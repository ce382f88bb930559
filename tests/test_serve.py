"""AOT policy-application serving (fast_autoaugment_tpu/serve/).

Covers the tentpole's serving pillar: AOT shape-padding correctness
(padded lanes never leak), bitwise equivalence of served outputs with
direct ``apply_policy`` application, the grouped batch kernel contract,
coalescer ordering/timeout behavior, and the CLI plumbing.  Tiny
8px images keep the augment-kernel compiles in the seconds; the
HTTP round-trip is ``slow``-marked per the tier-1 wall budget.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fast_autoaugment_tpu.ops.augment import (
    apply_policy,
    apply_policy_batch_grouped,
)
from fast_autoaugment_tpu.serve.policy_server import (
    AotPolicyApplier,
    PolicyServer,
    ServeError,
    pick_shape,
)


IMG = 8
SINGLE_SUB = np.array([[[4, 0.8, 0.7], [10, 0.5, 0.3]]], np.float32)
MULTI_SUB = np.array([
    [[4, 0.8, 0.7], [10, 0.5, 0.3]],
    [[0, 0.5, 0.5], [1, 0.5, 0.5]],
    [[8, 0.9, 0.2], [12, 0.4, 0.6]],
], np.float32)


def _images(n, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, 256, (n, IMG, IMG, 3)).astype(np.float32)


def _keys(n, base=0):
    return np.stack([np.asarray(jax.random.PRNGKey(base + i), np.uint32)
                     for i in range(n)])


@pytest.fixture(scope="module")
def applier_single():
    """One module-scoped exact single-sub applier (shapes 2 and 4) —
    shared across tests to pay the AOT compile once."""
    return AotPolicyApplier(SINGLE_SUB, image=IMG, shapes=(2, 4),
                            dispatch="auto")


# ------------------------------------------------------- shape picking


def test_pick_shape():
    assert pick_shape((1, 8, 32), 1) == 1
    assert pick_shape((1, 8, 32), 2) == 8
    assert pick_shape((1, 8, 32), 32) == 32
    with pytest.raises(ValueError):
        pick_shape((1, 8), 9)


def test_applier_validates_inputs(applier_single):
    assert applier_single.dispatch == "exact"  # auto: single sub
    with pytest.raises(ValueError):
        applier_single.apply(np.zeros((2, 4, 4, 3), np.float32), _keys(2))
    with pytest.raises(ValueError):
        AotPolicyApplier(np.zeros((3, 2)), image=IMG)
    with pytest.raises(ValueError):
        AotPolicyApplier(SINGLE_SUB, image=IMG, dispatch="nope")


# ------------------------------------------------ bitwise + pad safety


def test_exact_single_sub_bitwise_vs_apply_policy(applier_single):
    """The acceptance contract: served row i == direct
    apply_policy(image_i, policy, key_i), bitwise."""
    imgs, keys = _images(3), _keys(3)
    out = applier_single.apply(imgs, keys)
    ref = np.stack([
        np.asarray(apply_policy(jnp.asarray(imgs[i]),
                                jnp.asarray(SINGLE_SUB),
                                jnp.asarray(keys[i])))
        for i in range(3)])
    assert np.array_equal(out, ref)


def test_padding_never_leaks(applier_single):
    """The same images through two different padded shapes give
    identical results — lane i depends only on (image i, key i)."""
    imgs, keys = _images(2, seed=3), _keys(2, base=9)
    via_2 = applier_single.apply(imgs, keys)              # exact fit
    # force the 4-shape by batching with 1 extra then slicing
    imgs3 = np.concatenate([imgs, _images(1, seed=4)])
    via_4 = applier_single.apply(imgs3, _keys(3, base=9))[:2]
    assert np.array_equal(via_2, via_4)


def test_chunking_over_largest_shape(applier_single):
    """Batches above the largest AOT shape chunk transparently and
    stay bitwise with the per-image reference."""
    imgs, keys = _images(7, seed=5), _keys(7, base=20)
    out = applier_single.apply(imgs, keys)  # 4 + 3 across two dispatches
    ref = np.stack([
        np.asarray(apply_policy(jnp.asarray(imgs[i]),
                                jnp.asarray(SINGLE_SUB),
                                jnp.asarray(keys[i])))
        for i in range(7)])
    assert np.array_equal(out, ref)


@pytest.mark.slow
def test_exact_multi_sub_bitwise():
    """Multi-sub exact dispatch (the select-all lowering — compile-heavy,
    hence slow-marked) is still bitwise per-image apply_policy."""
    ap = AotPolicyApplier(MULTI_SUB, image=IMG, shapes=(4,),
                          dispatch="exact")
    imgs, keys = _images(3), _keys(3)
    out = ap.apply(imgs, keys)
    ref = np.stack([
        np.asarray(apply_policy(jnp.asarray(imgs[i]),
                                jnp.asarray(MULTI_SUB),
                                jnp.asarray(keys[i])))
        for i in range(3)])
    assert np.array_equal(out, ref)


@pytest.mark.slow
def test_grouped_matches_batch_kernel():
    """Grouped dispatch serves exactly what the PR-3 batch kernel
    produces on the padded batch (auto picks grouped for multi-sub)."""
    ap = AotPolicyApplier(MULTI_SUB, image=IMG, shapes=(4,),
                          dispatch="auto", groups=2)
    assert ap.dispatch == "grouped"
    imgs = _images(3)
    key = np.asarray(jax.random.PRNGKey(7), np.uint32)
    out = ap.apply(imgs, key)
    padded = np.concatenate([imgs, np.zeros((1, IMG, IMG, 3), np.float32)])
    ref = np.asarray(apply_policy_batch_grouped(
        jnp.asarray(padded), jnp.asarray(MULTI_SUB), jnp.asarray(key),
        groups=2))[:3]
    assert np.array_equal(out, ref)


@pytest.mark.slow
def test_export_serialize_roundtrip(applier_single):
    """jax.export round-trip: the serialized program reproduces the
    live executable bitwise at the exported padded shape."""
    from fast_autoaugment_tpu.serve.policy_server import deserialize_apply

    blob = applier_single.export_serialized()  # largest shape (4)
    fn = deserialize_apply(blob)
    imgs, keys = _images(4, seed=6), _keys(4, base=40)
    out = np.asarray(fn(imgs, keys))
    assert np.array_equal(out, applier_single.apply(imgs, keys))


# --------------------------------------------------------- coalescing


def test_server_coalesces_and_scatters_fifo(applier_single):
    srv = PolicyServer(applier_single, max_wait_ms=50).start()
    try:
        imgs, keys = _images(4, seed=7), _keys(4, base=50)
        p1 = srv.submit(imgs[:2], keys[:2])
        p2 = srv.submit(imgs[2:3], keys[2:3])
        p3 = srv.submit(imgs[3:4], keys[3:4])
        got = np.concatenate([srv.result(p1), srv.result(p2),
                              srv.result(p3)])
        assert np.array_equal(got, applier_single.apply(imgs, keys))
        st = srv.stats()
        assert st["requests"] == 3 and st["images_served"] == 4
        # 4 images <= max_batch 4: the window coalesced them into FEWER
        # dispatches than requests (usually exactly one)
        assert st["dispatches"] < 3
    finally:
        srv.stop()


def test_server_timeout_flushes_partial_batch(applier_single):
    """A lone request completes after max_wait_ms — the coalescer never
    waits for a batch that is not coming."""
    import time

    srv = PolicyServer(applier_single, max_wait_ms=30).start()
    try:
        t0 = time.perf_counter()
        out = srv.augment(_images(1, seed=8), _keys(1, base=60))
        wall = time.perf_counter() - t0
        assert out.shape == (1, IMG, IMG, 3)
        assert wall < 5.0  # one window + one dispatch, not forever
    finally:
        srv.stop()


def test_server_never_splits_a_request(applier_single):
    """A request that would overflow the batch is carried WHOLE to the
    next dispatch, preserving FIFO and per-request key contiguity."""
    srv = PolicyServer(applier_single, max_batch=4, max_wait_ms=40).start()
    try:
        imgs, keys = _images(6, seed=9), _keys(6, base=70)
        p1 = srv.submit(imgs[:3], keys[:3])   # 3
        p2 = srv.submit(imgs[3:6], keys[3:6])  # 3 -> carried (3+3 > 4)
        r1, r2 = srv.result(p1), srv.result(p2)
        assert np.array_equal(np.concatenate([r1, r2]),
                              applier_single.apply(imgs, keys))
        assert srv.stats()["dispatches"] >= 2
    finally:
        srv.stop()


def test_server_rejects_oversized_and_empty(applier_single):
    srv = PolicyServer(applier_single, max_batch=4)
    with pytest.raises(ValueError):
        srv.submit(_images(5), _keys(5))
    with pytest.raises(ValueError):
        srv.submit(np.zeros((0, IMG, IMG, 3), np.float32))


def test_server_error_propagates_to_caller(applier_single):
    """A failed dispatch surfaces as ServeError on every coalesced
    request instead of wedging the worker."""
    srv = PolicyServer(applier_single, max_wait_ms=10).start()
    try:
        # wrong spatial dims pass submit() but fail in the applier
        bad = srv.submit(np.zeros((1, 4, 4, 3), np.float32))
        with pytest.raises(ServeError):
            srv.result(bad, timeout=30.0)
        # the worker survives: the next request still completes
        assert srv.augment(_images(1, seed=11)).shape == (1, IMG, IMG, 3)
    finally:
        srv.stop()


def test_server_stop_drains_queue(applier_single):
    srv = PolicyServer(applier_single, max_wait_ms=10).start()
    srv.stop()
    p = srv._q  # after stop, a late submit is answered with an error
    assert p.empty()


# ----------------------------------------------------------- serve_cli


def test_build_policy_tensor_from_json_and_archive(tmp_path):
    from fast_autoaugment_tpu.serve.serve_cli import build_policy_tensor

    subs = [[["Rotate", 0.5, 0.4], ["Invert", 0.2, 0.0]],
            [["ShearX", 0.9, 0.1], ["Solarize", 0.3, 0.7]]]
    path = tmp_path / "final_policy.json"
    path.write_text(json.dumps(subs))
    t = build_policy_tensor(str(path))
    assert t.shape == (2, 2, 3) and t.dtype == np.float32
    assert t[0, 0, 0] == 4.0  # Rotate's op index

    t2 = build_policy_tensor("fa_reduced_cifar10")
    assert t2.ndim == 3 and t2.shape[0] > 100  # the shipped archive

    (tmp_path / "empty.json").write_text("[]")
    with pytest.raises(ValueError):
        build_policy_tensor(str(tmp_path / "empty.json"))


def test_serve_cli_parser_defaults():
    from fast_autoaugment_tpu.serve.serve_cli import build_parser

    args = build_parser().parse_args(["--policy", "x.json"])
    assert args.dispatch == "auto"
    # the cache is placed from outside, never by a flag
    assert not hasattr(args, "compile_cache")
    assert args.shapes == "1,8,32,128" and args.max_wait_ms == 5.0


def test_seed_keys_are_prngkeys():
    from fast_autoaugment_tpu.serve.serve_cli import _seed_keys

    keys = _seed_keys([0, 1, 2])
    assert keys.shape == (3, 2) and keys.dtype == np.uint32
    assert np.array_equal(keys[1], np.asarray(jax.random.PRNGKey(1),
                                              np.uint32))


@pytest.mark.slow
def test_http_roundtrip(tmp_path):
    """End-to-end over HTTP: POST an npz with seeds, the response is
    bitwise the direct apply_policy application (uint8-clipped)."""
    import http.client
    import io
    import threading
    from http.server import ThreadingHTTPServer

    from fast_autoaugment_tpu.serve.serve_cli import _seed_keys, make_handler

    applier = AotPolicyApplier(SINGLE_SUB, image=IMG, shapes=(4,))
    srv = PolicyServer(applier, max_wait_ms=5).start()
    httpd = ThreadingHTTPServer(("127.0.0.1", 0),
                                make_handler(srv, applier))
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    try:
        port = httpd.server_address[1]
        imgs = _images(3, seed=12).astype(np.uint8)
        buf = io.BytesIO()
        np.savez(buf, images=imgs, seeds=np.arange(3))
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
        conn.request("POST", "/augment", body=buf.getvalue())
        resp = conn.getresponse()
        assert resp.status == 200, resp.read()
        got = np.load(io.BytesIO(resp.read()))["images"]
        keys = _seed_keys(np.arange(3))
        ref = np.clip(applier.apply(imgs.astype(np.float32), keys),
                      0, 255).astype(np.uint8)
        assert np.array_equal(got, ref)

        conn.request("GET", "/stats")
        stats = json.loads(conn.getresponse().read())
        assert stats["images_served"] == 3
        assert "compile_cache" in stats and "aot_compile" in stats

        conn.request("GET", "/healthz")
        assert json.loads(conn.getresponse().read())["ok"] is True
    finally:
        httpd.shutdown()
        httpd.server_close()
        srv.stop()
