"""Donation correctness for the zero-copy serving data plane
(fast_autoaugment_tpu/serve/policy_server.py ``donate=True`` +
``PolicyServer(double_buffer=True)``).

The invariants pinned here, bitwise across every AOT shape:

- donated dispatch serves the SAME bytes as the undonated PR-7 path —
  donation may only change buffer ownership, never results;
- a donated input staging buffer is never read after dispatch: the
  materialized result owns its memory (mutating the staging arrays
  afterwards cannot corrupt an already-returned batch);
- the two standing staging buffers never alias, and batch k+1's
  staging never overwrites batch k's still-in-flight input (the
  double-buffer invariant the pipelined server relies on);
- pad rows are zeroed on every reuse — a poisoned (previously used)
  staging buffer must not leak old pixels into the padded lanes;
- the CPU fallback is silent: donation is ignored-with-a-filtered-
  warning on backends without buffer donation, not a per-dispatch
  warning spray.

Tiny 8px images and shapes (2, 4) keep the extra AOT compiles in the
tier-1 seconds budget.
"""

import warnings

import jax
import numpy as np
import pytest

from fast_autoaugment_tpu.serve.policy_server import (
    AotPolicyApplier,
    PolicyServer,
)

IMG = 8
SINGLE_SUB = np.array([[[4, 0.8, 0.7], [10, 0.5, 0.3]]], np.float32)
MULTI_SUB = np.array([
    [[4, 0.8, 0.7], [10, 0.5, 0.3]],
    [[0, 0.5, 0.5], [1, 0.5, 0.5]],
], np.float32)


def _images(n, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, 256, (n, IMG, IMG, 3)).astype(np.float32)


def _keys(n, base=0):
    return np.stack([np.asarray(jax.random.PRNGKey(base + i), np.uint32)
                     for i in range(n)])


@pytest.fixture(scope="module")
def plain():
    """The undonated PR-7 reference applier (exact, single-sub)."""
    return AotPolicyApplier(SINGLE_SUB, image=IMG, shapes=(2, 4),
                            dispatch="exact")


@pytest.fixture(scope="module")
def donated():
    """Same policy/shapes, donated + double-buffered staging."""
    return AotPolicyApplier(SINGLE_SUB, image=IMG, shapes=(2, 4),
                            dispatch="exact", donate=True)


def test_donated_matches_undonated_bitwise_every_shape(plain, donated):
    # every batch size across both AOT shapes, including the padded
    # ones (n=1 pads to 2, n=3 pads to 4) and the exact fits
    for n in (1, 2, 3, 4):
        imgs, keys = _images(n, seed=n), _keys(n, base=10 * n)
        want = plain.apply(imgs.copy(), keys)
        got = donated.apply(imgs.copy(), keys)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_grouped_donated_matches_undonated():
    imgs = _images(3, seed=7)
    key = np.asarray(jax.random.PRNGKey(3), np.uint32)
    plain_g = AotPolicyApplier(MULTI_SUB, image=IMG, shapes=(4,),
                               dispatch="grouped", groups=2)
    don_g = AotPolicyApplier(MULTI_SUB, image=IMG, shapes=(4,),
                             dispatch="grouped", groups=2, donate=True)
    np.testing.assert_array_equal(
        np.asarray(don_g.apply(imgs.copy(), key)),
        np.asarray(plain_g.apply(imgs.copy(), key)))


def test_multichunk_donated_matches_undonated(plain, donated):
    # n > max AOT shape: the chunked path forces each donated chunk
    # synchronous (two slots only guarantee one overlap step)
    imgs, keys = _images(7, seed=21), _keys(7, base=70)
    np.testing.assert_array_equal(
        np.asarray(donated.apply(imgs.copy(), keys)),
        np.asarray(plain.apply(imgs.copy(), keys)))


def test_pad_rows_never_leak_from_reused_staging(plain, donated):
    # poison BOTH standing slots with old pixels, then serve padded
    # batches twice (hitting both slots): results must match the
    # fresh-allocation path bitwise — the pad lanes were re-zeroed
    for s, bufs in donated._staging.items():
        for buf in bufs:
            buf.fill(123.0)
    for rep in range(2):
        imgs, keys = _images(3, seed=30 + rep), _keys(3, base=300 + rep)
        np.testing.assert_array_equal(
            np.asarray(donated.apply(imgs.copy(), keys)),
            np.asarray(plain.apply(imgs.copy(), keys)))


def test_result_does_not_alias_staging(donated):
    imgs, keys = _images(2, seed=5), _keys(2, base=50)
    out = np.asarray(donated.apply(imgs, keys))
    ref = out.copy()
    # scribble over every staging buffer AFTER the apply returned: a
    # result that aliased host staging would corrupt here
    for bufs in donated._staging.values():
        for buf in bufs:
            buf.fill(-1.0)
    for kbufs in donated._staging_keys.values():
        for kbuf in kbufs:
            kbuf.fill(0)
    np.testing.assert_array_equal(out, ref)
    for bufs in donated._staging.values():
        for buf in bufs:
            assert not np.shares_memory(out, buf)


def test_double_buffers_are_distinct_arrays(donated):
    for s, bufs in donated._staging.items():
        assert len(bufs) == 2
        assert bufs[0] is not bufs[1]
        assert not np.shares_memory(bufs[0], bufs[1])


def test_inflight_batch_survives_next_stage(plain, donated):
    # the pipelined server's exact overlap shape: dispatch batch A,
    # stage + dispatch batch B while A is still in flight, THEN
    # materialize A — B's staging must not have overwritten A's input
    a_imgs, a_keys = _images(2, seed=41), _keys(2, base=410)
    b_imgs, b_keys = _images(2, seed=42), _keys(2, base=420)
    want_a = np.asarray(plain.apply(a_imgs.copy(), a_keys))
    want_b = np.asarray(plain.apply(b_imgs.copy(), b_keys))
    h_a = donated.apply_async(a_imgs.copy(), a_keys)
    h_b = donated.apply_async(b_imgs.copy(), b_keys)
    np.testing.assert_array_equal(np.asarray(h_a.materialize()), want_a)
    np.testing.assert_array_equal(np.asarray(h_b.materialize()), want_b)


def test_donating_applier_compiles_without_a_donation_warning():
    # the installed backend implements donation (a donated input is
    # deleted by the dispatch), so the compile seam filters nothing:
    # compiling a donating applier and serving with it must not surface
    # a "donation is not implemented" warning — if one appears, the
    # backend stopped honoring the aliasing and donate=True is a no-op.
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        app = AotPolicyApplier(SINGLE_SUB, image=IMG, shapes=(2,),
                               dispatch="exact", donate=True)
        app.apply(_images(2, seed=9), _keys(2, base=90))
    spray = [w for w in caught
             if "donat" in str(w.message).lower()]
    assert spray == []


def test_double_buffered_server_matches_sequential(plain, donated):
    # end to end through the coalescer: a pipelined double-buffered
    # server over the donated applier serves the same bytes as the
    # strictly sequential default server over the undonated applier
    seq = PolicyServer(plain, max_wait_ms=1.0).start()
    dbuf = PolicyServer(donated, max_wait_ms=1.0,
                        double_buffer=True).start()
    try:
        batches = [( _images(n, seed=60 + n), _keys(n, base=600 + n))
                   for n in (1, 2, 3, 2)]
        want = [np.asarray(seq.result(seq.submit(i.copy(), k)))
                for i, k in batches]
        pend = [dbuf.submit(i.copy(), k) for i, k in batches]
        got = [np.asarray(dbuf.result(p, timeout=60.0)) for p in pend]
        for w, g in zip(want, got):
            np.testing.assert_array_equal(g, w)
        stats = dbuf.stats()
        assert stats["data_plane"] == {"donate": True,
                                       "double_buffer": True}
    finally:
        seq.stop()
        dbuf.stop()
