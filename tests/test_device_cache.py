"""Device-resident dataset cache + multi-step fused train dispatch:
index-matrix parity with the host iterators, DeviceCache placement,
dispatch chunk clamping, multistep scan parity (sequential + stacked),
trainer-level checkpoint equivalence and resume across dispatch
boundaries, lazy force-off, driver stamping/accounting, CLI flags."""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from fast_autoaugment_tpu.core.config import Config
from fast_autoaugment_tpu.data.datasets import ArrayDataset


def _conf(**over):
    base = {
        "model": {"type": "wresnet10_1"},
        "dataset": "synthetic",
        "aug": "default",
        "cutout": 8,
        "batch": 8,
        "epoch": 1,
        "lr": 0.05,
        "lr_schedule": {"type": "cosine", "warmup": {"multiplier": 2, "epoch": 1}},
        "optimizer": {"type": "sgd", "decay": 2e-4, "clip": 5.0,
                      "momentum": 0.9, "nesterov": True},
    }
    base.update(over)
    return Config(base)


def _dataset(n=64, img=4, seed=0):
    rng = np.random.default_rng(seed)
    return ArrayDataset(
        rng.integers(0, 256, (n, img, img, 3), dtype=np.uint8),
        rng.integers(0, 10, (n,), np.int32), 10)


# ------------------------------------------------- index-matrix parity

def test_train_index_matrix_matches_train_batches():
    """The matrix IS what train_batches walks — row s must equal the
    s-th yielded batch's indices (same permutation, drop-last, shard)."""
    from fast_autoaugment_tpu.data.pipeline import (
        train_batches,
        train_index_matrix,
    )

    ds = _dataset(70)
    idx = np.arange(13, 61)
    mat = train_index_matrix(idx, 8, epoch=5, seed=3)
    assert mat.shape == (6, 8)  # 48 // 8, drop-last
    got = list(train_batches(ds, idx, 8, epoch=5, seed=3))
    assert len(got) == len(mat)
    for row, (x, y) in zip(mat, got):
        np.testing.assert_array_equal(x, ds.images[row])
        np.testing.assert_array_equal(y, ds.labels[row])
    # per-process sharding: each process's matrix is its contiguous shard
    m0 = train_index_matrix(idx, 8, epoch=5, seed=3,
                            process_index=0, process_count=2)
    m1 = train_index_matrix(idx, 8, epoch=5, seed=3,
                            process_index=1, process_count=2)
    np.testing.assert_array_equal(np.concatenate([m0, m1], axis=1), mat)


def test_stacked_index_matrix_matches_stacked_batches():
    from fast_autoaugment_tpu.data.pipeline import (
        stacked_index_matrix,
        stacked_train_batches,
    )

    ds = _dataset(64)
    folds = [np.arange(32), np.arange(16)]  # 4 vs 2 steps at batch 8
    chunks, active = stacked_index_matrix(folds, 8, epoch=2, seeds=[0, 7])
    assert chunks.shape == (4, 2, 8) and active.shape == (4, 2)
    np.testing.assert_array_equal(active[:, 1], [1, 1, 0, 0])
    for s, (x, y, a) in enumerate(
            stacked_train_batches(ds, folds, 8, epoch=2, seeds=[0, 7])):
        np.testing.assert_array_equal(a, active[s])
        np.testing.assert_array_equal(x, ds.images[chunks[s]])
        np.testing.assert_array_equal(y, ds.labels[chunks[s]])


def test_split_dispatch_chunks_clamps_remainder():
    from fast_autoaugment_tpu.data.pipeline import split_dispatch_chunks

    assert split_dispatch_chunks(10, 1) == [1] * 10
    assert split_dispatch_chunks(10, 4) == [4, 4, 2]
    assert split_dispatch_chunks(4, 4) == [4]
    assert split_dispatch_chunks(3, 8) == [3]  # N clamped to the epoch
    assert split_dispatch_chunks(0, 4) == []
    with pytest.raises(ValueError, match="steps_per_dispatch"):
        split_dispatch_chunks(10, 0)


# --------------------------------------------- cache placement/resolve

def test_device_cache_contents_and_padding(devices8):
    from fast_autoaugment_tpu.data.pipeline import DeviceCache
    from fast_autoaugment_tpu.parallel.mesh import make_mesh

    ds = _dataset(n=13)  # not a multiple of 8 devices -> padded
    cache = DeviceCache(ds, make_mesh(devices8))
    assert cache.num_examples == 13
    # stored as one row an image, the example axis first and padded
    assert cache.images.shape == (16, 4 * 4 * 3) and cache.labels.shape[0] == 16
    assert cache.images.example_shape == (4, 4, 3)
    np.testing.assert_array_equal(
        np.asarray(cache.images.take(np.arange(13))), ds.images)
    np.testing.assert_array_equal(np.asarray(cache.labels)[:13], ds.labels)
    assert not np.any(np.asarray(cache.images.rows)[13:])  # zero pad rows
    assert cache.nbytes == ds.images.nbytes + ds.labels.nbytes
    lazy = ArrayDataset(np.asarray(["a.jpg"] * 4, object),
                        np.zeros(4, np.int32), 10, lazy=True)
    with pytest.raises(ValueError, match="in-memory"):
        DeviceCache(lazy, make_mesh(devices8))


#: name -> (examples' shape, dtype, index shape): what the trainers store
#: and how they ask for it.  ``N`` = 203 divides by no mesh of 2, 4 or 8
#: devices, so every mesh but a single device pads it.
STORED_FORM_CASES = {
    "cifar_uint8": ((203, 32, 32, 3), np.uint8, (16,)),
    "row_4_does_not_divide_uint8": ((203, 5, 7, 3), np.uint8, (16,)),
    "token_ids_int32": ((203, 33), np.int32, (8,)),
    "stacked_index": ((203, 32, 32, 3), np.uint8, (3, 8)),
    "padded_n": ((13, 4, 4, 3), np.uint8, (2, 4)),
}


def check_taken_batch_is_images_idx(case: str, devices=None) -> None:
    """A batch taken inside a compiled program from the cache's stored
    form is ``images[idx]`` bit for bit: same bytes in the same order,
    same shape, same dtype.  On whatever backend ``jax.devices()``
    gives (the chip runs it once by importing this module: it is the
    only check of byte order there — `learned` and the logits
    comparison would pass permuted bytes)."""
    from fast_autoaugment_tpu.data.pipeline import DeviceCache
    from fast_autoaugment_tpu.parallel.mesh import make_mesh

    shape, dtype, idx_shape = STORED_FORM_CASES[case]
    rng = np.random.default_rng(len(case))
    high = 256 if dtype == np.uint8 else 20480
    images = rng.integers(0, high, shape).astype(dtype)
    # a batch in no order, the last example (next to the pad rows) and
    # the first among it
    idx = rng.integers(0, shape[0], idx_shape).astype(np.int32)
    idx.flat[:2] = (shape[0] - 1, 0)
    ds = ArrayDataset(images, rng.integers(0, 10, shape[:1]).astype(np.int32), 10)
    cache = DeviceCache(ds, make_mesh(devices or jax.devices()))
    # the rule: rank above 2 is flattened behind the example axis, rank
    # 2 stays as it is; no dtype changes
    assert cache.images.rows.ndim == 2 and cache.images.dtype == dtype
    assert cache.images.shape[1] == int(np.prod(shape[1:]))
    assert cache.images.example_shape == shape[1:]
    taken = jax.jit(lambda stored, i: stored.take(i))(cache.images, idx)
    assert taken.shape == idx_shape + shape[1:] and taken.dtype == dtype
    np.testing.assert_array_equal(np.asarray(taken), images[idx])


@pytest.mark.parametrize("case", sorted(STORED_FORM_CASES))
def test_taken_batch_is_images_idx(case, devices8):
    check_taken_batch_is_images_idx(case, devices8)


def test_stored_rows_is_a_view_and_crosses_jit_as_its_rows():
    from fast_autoaugment_tpu.data.pipeline import StoredRows

    images = _dataset(n=6).images
    stored = StoredRows.of(images)
    assert np.shares_memory(stored.rows, images)  # a reshape, no copy
    ids = np.arange(12, dtype=np.int32).reshape(4, 3)
    assert StoredRows.of(ids).rows is ids and StoredRows.of(ids).example_shape == (3,)
    leaves, treedef = jax.tree.flatten(stored)
    assert len(leaves) == 1 and leaves[0] is stored.rows
    again = jax.tree.unflatten(treedef, leaves)
    assert again.example_shape == (4, 4, 3) and again.shape == (6, 48)


def test_train_dispatch_lays_out_nothing_but_the_batch():
    """The lowered ``train_dispatch`` program at CIFAR's shapes: the
    cache argument is consumed by the gather alone, and no tensor of the
    whole cache's element count has a rank above 2 — what the CPU can pin
    of "only batch-sized arrays are laid out again" (on the v5e a rank-4
    ``uint8 [50000, 32, 32, 3]`` argument drew a ``copy`` of all of it in
    front of every step's gather: PERF.md section 6, PR 37)."""
    import re

    from fast_autoaugment_tpu.data.pipeline import StoredRows
    from fast_autoaugment_tpu.models import get_model
    from fast_autoaugment_tpu.ops.optim import build_optimizer
    from fast_autoaugment_tpu.train.steps import (
        create_train_state,
        make_multistep_train_step,
        make_train_step_body,
    )

    n, image, batch = 50000, (32, 32, 3), 8
    model = get_model({"type": "wresnet10_1"}, 10)
    opt = build_optimizer(dict(_conf()["optimizer"]), lambda s: 0.05)
    body = make_train_step_body(model, opt, num_classes=10, cutout_length=16,
                                use_policy=False)
    state = jax.eval_shape(lambda: create_train_state(
        model, opt, jax.random.PRNGKey(0),
        jnp.zeros((2,) + image, jnp.float32), use_ema=False))
    spec = jax.ShapeDtypeStruct
    cache = jax.tree.map(lambda rows: spec((n,) + rows.shape[1:], rows.dtype),
                         StoredRows.of(np.zeros((1,) + image, np.uint8)))
    text = make_multistep_train_step(body, steps_per_dispatch=1).lower(
        state, cache, spec((n,), jnp.int32), spec((1, batch), jnp.int32),
        spec((1, 1, 3), jnp.float32), spec((2,), jnp.uint32)).as_text()

    whole = n * int(np.prod(image))
    seen = set()
    for dims in re.findall(r"tensor<((?:\d+x)+)[a-z]+\d+>", text):
        shape = tuple(int(d) for d in dims[:-1].split("x"))
        if int(np.prod(shape)) == whole:
            seen.add(shape)
    assert seen == {(n, 3072)}, seen
    cache_type = "tensor<50000x3072xui8>"
    users = [line.strip() for line in text.splitlines()
             if cache_type in line and "func.func" not in line]
    assert users, "the cache argument is not in the program"
    for line in users:
        takes = re.search(r"= call @_take\w*\(%arg\d+,", line)
        gathers = re.search(r'= "stablehlo.gather"\(%arg\d+,', line)
        assert takes or gathers, line
    assert sum("stablehlo.gather" in line for line in users) == 1


def test_resolve_device_cache_gates():
    from fast_autoaugment_tpu.data.pipeline import resolve_device_cache

    eager = _dataset(4)
    lazy = ArrayDataset(np.asarray(["a.jpg"] * 4, object),
                        np.zeros(4, np.int32), 10, lazy=True)
    assert resolve_device_cache("auto", eager) is True
    assert resolve_device_cache("auto", lazy) is False  # lazy forces off
    assert resolve_device_cache("auto", eager, process_count=2) is False
    assert resolve_device_cache("off", eager) is False
    assert resolve_device_cache("on", eager) is True
    with pytest.raises(ValueError, match="lazy"):
        resolve_device_cache("on", lazy)  # explicit ask fails LOUDLY
    with pytest.raises(ValueError, match="multi-host"):
        resolve_device_cache("on", eager, process_count=2)
    with pytest.raises(ValueError, match="unknown device-cache"):
        resolve_device_cache("maybe", eager)


def test_place_index_matrix_shapes(devices8):
    from fast_autoaugment_tpu.parallel.mesh import (
        make_fold_mesh,
        make_mesh,
        place_index_matrix,
        place_stacked_index_matrix,
    )

    idx = np.arange(16).reshape(2, 8)
    dev = place_index_matrix(make_mesh(devices8), idx)
    assert dev.shape == (2, 8) and dev.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(dev), idx)
    mesh = make_fold_mesh(2, devices8)
    st = np.arange(32).reshape(2, 2, 8)
    act = np.ones((2, 2), np.float32)
    i_dev, a_dev = place_stacked_index_matrix(mesh, st, act)
    assert i_dev.shape == (2, 2, 8) and a_dev.shape == (2, 2)
    np.testing.assert_array_equal(np.asarray(i_dev), st)


def test_steps_per_dispatch_requires_cache(tmp_path):
    from fast_autoaugment_tpu.train.trainer import train_and_eval

    with pytest.raises(ValueError, match="needs the device"):
        train_and_eval(_conf(), str(tmp_path), test_ratio=0.4,
                       device_cache="off", steps_per_dispatch=4)


# ------------------------------------------------- multistep step parity

@pytest.mark.slow
def test_multistep_n1_bitwise_matches_host_step(devices8):
    """The N=1 multistep program (gather + body, no scan) from the
    device cache is BIT-FOR-BIT the host-fed jitted step — the property
    that makes the default flags a pure transport change.  Slow-marked
    per the tier-1 wall-budget discipline (compile-heavy; the slow
    trainer-level default-equivalence test pins the same property
    end-to-end)."""
    from fast_autoaugment_tpu.data.pipeline import (
        DeviceCache,
        train_index_matrix,
    )
    from fast_autoaugment_tpu.models import get_model
    from fast_autoaugment_tpu.ops.optim import build_optimizer
    from fast_autoaugment_tpu.parallel.mesh import (
        make_mesh,
        place_index_matrix,
        replicated,
        shard_batch,
    )
    from fast_autoaugment_tpu.train.steps import (
        create_train_state,
        make_multistep_train_step,
        make_train_step,
        make_train_step_body,
    )

    mesh = make_mesh(devices8)
    model = get_model({"type": "wresnet10_1"}, 10)
    opt_conf = dict(_conf()["optimizer"])
    kw = dict(num_classes=10, cutout_length=4, use_policy=False)
    sample = jnp.zeros((2, 8, 8, 3), jnp.float32)
    ds = _dataset(n=64, img=8)
    pol = jnp.zeros((1, 1, 3), jnp.float32)
    key = jax.random.PRNGKey(3)
    mat = train_index_matrix(np.arange(64), 16, epoch=1, seed=0)  # 4 steps

    def fresh():
        opt = build_optimizer(opt_conf, lambda s: 0.05)
        return create_train_state(model, opt, jax.random.PRNGKey(0), sample,
                                  use_ema=False)

    opt = build_optimizer(opt_conf, lambda s: 0.05)
    host_step = make_train_step(model, opt, **kw)
    s_host = fresh()
    for row in mat:
        b = shard_batch(mesh, {"x": ds.images[row], "y": ds.labels[row]})
        s_host, m_host = host_step(s_host, b["x"], b["y"], pol, key)

    cache = DeviceCache(ds, mesh)
    multi = make_multistep_train_step(
        make_train_step_body(model, opt, **kw), steps_per_dispatch=1)
    rep = replicated(mesh)
    s_dev = jax.device_put(fresh(), rep)
    pol_c, key_c = jax.device_put(pol, rep), jax.device_put(key, rep)
    for row in mat:
        s_dev, m_dev = multi(s_dev, cache.images, cache.labels,
                             place_index_matrix(mesh, row[None]), pol_c, key_c)
    for a, b in zip(jax.tree.leaves(s_host), jax.tree.leaves(s_dev)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for k in m_host:
        np.testing.assert_array_equal(np.asarray(m_host[k]),
                                      np.asarray(m_dev[k]))


@pytest.mark.slow
def test_multistep_scan_parity_sequential_and_stacked(devices8):
    """N>1 parity for both bodies, rolled AND unrolled: the fused
    program matches the per-step path to the documented ~1 f32 ULP/step
    bound — the fold-stacking deviation class (fusing several steps into
    one program lets XLA reorder sharded-kernel reductions across them,
    with or without a while loop; only N=1 is bitwise, which is why it
    is the default).  Stacked lanes that go inactive mid-dispatch do
    not take the masked step."""
    from fast_autoaugment_tpu.data.pipeline import DeviceCache
    from fast_autoaugment_tpu.models import get_model
    from fast_autoaugment_tpu.ops.optim import build_optimizer
    from fast_autoaugment_tpu.parallel.mesh import make_mesh, replicated
    from fast_autoaugment_tpu.train.steps import (
        create_train_state,
        default_dispatch_unroll,
        make_multistep_train_step,
        make_stacked_step_body,
        make_stacked_train_step,
        make_train_step_body,
        slice_state,
        stack_states,
    )

    assert default_dispatch_unroll(4) == 4  # cpu backend: full unroll
    mesh = make_mesh(devices8)
    rep = replicated(mesh)
    model = get_model({"type": "wresnet10_1"}, 10)
    opt_conf = dict(_conf()["optimizer"])
    kw = dict(num_classes=10, cutout_length=4, use_policy=False)
    sample = jnp.zeros((2, 8, 8, 3), jnp.float32)
    ds = _dataset(n=64, img=8)
    pol = jax.device_put(jnp.zeros((1, 1, 3), jnp.float32), rep)
    key = jax.device_put(jax.random.PRNGKey(3), rep)
    rng = np.random.default_rng(1)

    def fresh(seed=0):
        opt = build_optimizer(opt_conf, lambda s: 0.05)
        return create_train_state(model, opt, jax.random.PRNGKey(seed),
                                  sample, use_ema=False)

    opt = build_optimizer(opt_conf, lambda s: 0.05)
    cache = DeviceCache(ds, mesh)
    body = make_train_step_body(model, opt, **kw)
    mat = rng.permutation(64)[:4 * 16].reshape(4, 16)

    multi1 = make_multistep_train_step(body, steps_per_dispatch=1)
    s1 = jax.device_put(fresh(), rep)
    for row in mat:
        s1, _ = multi1(s1, cache.images, cache.labels,
                       jnp.asarray(row[None], jnp.int32), pol, key)
    for n_label, unroll in (("unrolled", None), ("rolled", 1)):
        multi4 = make_multistep_train_step(body, steps_per_dispatch=4,
                                           unroll=unroll)
        s4 = jax.device_put(fresh(), rep)
        s4, _ = multi4(s4, cache.images, cache.labels,
                       jnp.asarray(mat, jnp.int32), pol, key)
        for a, b in zip(jax.tree.leaves(s1.params), jax.tree.leaves(s4.params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6,
                                       err_msg=n_label)
        assert int(s4.step) == 4

    # stacked: scan outside the fold vmap, inactive lane frozen bitwise
    K = 2
    st_body = make_stacked_step_body(model, opt, **kw)
    st_step = make_stacked_train_step(model, opt, **kw)
    keys = jax.device_put(
        jnp.stack([jax.random.PRNGKey(100 + k) for k in range(K)]), rep)
    idx_st = rng.permutation(64)[:2 * K * 8].reshape(2, K, 8)
    act = np.asarray([[1.0, 1.0], [1.0, 0.0]], np.float32)  # lane 1 dies
    s_ref = stack_states([fresh(k) for k in range(K)])
    for t in range(2):
        s_ref, _ = st_step(s_ref, jnp.asarray(ds.images[idx_st[t]]),
                           jnp.asarray(ds.labels[idx_st[t]]),
                           jnp.zeros((1, 1, 3), jnp.float32), keys,
                           jnp.asarray(act[t]))
    multi_st = make_multistep_train_step(st_body, steps_per_dispatch=2,
                                         stacked=True)
    s_st = jax.device_put(stack_states([fresh(k) for k in range(K)]), rep)
    s_st, metrics = multi_st(s_st, cache.images, cache.labels,
                             jnp.asarray(idx_st, jnp.int32), pol, keys,
                             jnp.asarray(act))
    for k in range(K):
        for a, b in zip(jax.tree.leaves(slice_state(s_ref, k).params),
                        jax.tree.leaves(slice_state(s_st, k).params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6)
    assert int(slice_state(s_st, 1).step) == 1  # masked step not taken
    assert int(slice_state(s_st, 0).step) == 2
    assert metrics["num"].shape == (K,)


# --------------------------------------------- trainer-level equivalence

@pytest.mark.slow
def test_trainer_device_cache_default_bitwise_equivalence(tmp_path, devices8):
    """The acceptance pin: default flags (device_cache=auto,
    steps_per_dispatch=1) on an eager dataset produce a BIT-FOR-BIT
    identical checkpoint to the host-fed path (and the replayed eval
    split reports identical metrics)."""
    from fast_autoaugment_tpu.core.checkpoint import load_checkpoint
    from fast_autoaugment_tpu.models import get_model
    from fast_autoaugment_tpu.ops.optim import build_optimizer
    from fast_autoaugment_tpu.parallel.mesh import make_mesh
    from fast_autoaugment_tpu.train.steps import create_train_state
    from fast_autoaugment_tpu.train.trainer import train_and_eval

    conf = _conf()
    tmp = str(tmp_path)
    mesh = make_mesh(devices8)
    r_off = train_and_eval(conf, tmp, test_ratio=0.4, cv_fold=0,
                           save_path=f"{tmp}/off.msgpack", metric="last",
                           seed=0, evaluation_interval=1, mesh=mesh,
                           device_cache="off")
    r_on = train_and_eval(conf, tmp, test_ratio=0.4, cv_fold=0,
                          save_path=f"{tmp}/on.msgpack", metric="last",
                          seed=0, evaluation_interval=1, mesh=mesh,
                          device_cache="auto")
    model = get_model({"type": "wresnet10_1"}, 10)
    opt = build_optimizer(dict(conf["optimizer"]), lambda s: 0.0)
    tmpl = create_train_state(model, opt, jax.random.PRNGKey(0),
                              jnp.zeros((2, 32, 32, 3)), use_ema=False)
    a = load_checkpoint(f"{tmp}/off.msgpack", tmpl)
    b = load_checkpoint(f"{tmp}/on.msgpack", tmpl)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    for k in ("top1_valid", "loss_valid", "top1_test", "top1_train"):
        assert r_off[k] == pytest.approx(r_on[k], abs=1e-6), k


@pytest.mark.slow
def test_trainer_resume_across_dispatch_boundary(tmp_path, devices8):
    """Epoch boundaries stay dispatch boundaries when N does not divide
    steps_per_epoch (clamped remainder chunk): a run interrupted at the
    epoch-1 checkpoint and resumed with the SAME N reproduces the
    uninterrupted 2-epoch run exactly."""
    import shutil

    from fast_autoaugment_tpu.core.checkpoint import load_checkpoint
    from fast_autoaugment_tpu.models import get_model
    from fast_autoaugment_tpu.ops.optim import build_optimizer
    from fast_autoaugment_tpu.parallel.mesh import make_mesh
    from fast_autoaugment_tpu.train.steps import create_train_state
    from fast_autoaugment_tpu.train.trainer import train_and_eval

    conf = _conf(epoch=2)
    tmp = str(tmp_path)
    mesh = make_mesh(devices8)
    # synthetic: 512 examples, test_ratio 0.4 -> 307 train; global batch
    # 64 -> 4 steps/epoch; N=3 -> chunks [3, 1] every epoch
    kw = dict(test_ratio=0.4, cv_fold=0, metric="last", seed=0,
              evaluation_interval=1, mesh=mesh, device_cache="auto",
              steps_per_dispatch=3)
    train_and_eval(conf, tmp, save_path=f"{tmp}/full.msgpack", **kw)
    train_and_eval(_conf(epoch=1), tmp, save_path=f"{tmp}/part.msgpack", **kw)
    shutil.copy(f"{tmp}/part.msgpack", f"{tmp}/resumed.msgpack")
    shutil.copy(f"{tmp}/part.msgpack.meta.json",
                f"{tmp}/resumed.msgpack.meta.json")
    train_and_eval(conf, tmp, save_path=f"{tmp}/resumed.msgpack", **kw)

    model = get_model({"type": "wresnet10_1"}, 10)
    opt = build_optimizer(dict(conf["optimizer"]), lambda s: 0.0)
    tmpl = create_train_state(model, opt, jax.random.PRNGKey(0),
                              jnp.zeros((2, 32, 32, 3)), use_ema=False)
    a = load_checkpoint(f"{tmp}/full.msgpack", tmpl)
    b = load_checkpoint(f"{tmp}/resumed.msgpack", tmpl)
    assert int(a.step) == int(b.step) == 8
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.slow
def test_stacked_trainer_cache_matches_host(tmp_path, devices8):
    """train_folds_stacked with the cache + N=2 lands per-fold
    checkpoints matching the host-fed stacked path within the
    documented multi-step bound (ULP-level per-dispatch differences
    amplified over the epoch — the PR-2 trainer-equivalence class and
    tolerances)."""
    from fast_autoaugment_tpu.core.checkpoint import load_checkpoint, read_metadata
    from fast_autoaugment_tpu.models import get_model
    from fast_autoaugment_tpu.ops.optim import build_optimizer
    from fast_autoaugment_tpu.parallel.mesh import make_fold_mesh
    from fast_autoaugment_tpu.train.steps import create_train_state
    from fast_autoaugment_tpu.train.trainer import train_folds_stacked

    conf = _conf()
    tmp = str(tmp_path)
    host_paths = [os.path.join(tmp, f"h{f}.msgpack") for f in (0, 1)]
    cache_paths = [os.path.join(tmp, f"c{f}.msgpack") for f in (0, 1)]
    train_folds_stacked(
        conf, tmp, cv_ratio=0.4, folds=[0, 1], save_paths=host_paths, seed=0,
        evaluation_interval=1, mesh=make_fold_mesh(2, devices8, fold_shards=1),
        device_cache="off")
    train_folds_stacked(
        conf, tmp, cv_ratio=0.4, folds=[0, 1], save_paths=cache_paths, seed=0,
        evaluation_interval=1, mesh=make_fold_mesh(2, devices8, fold_shards=1),
        device_cache="auto", steps_per_dispatch=2)
    model = get_model({"type": "wresnet10_1"}, 10)
    opt = build_optimizer(dict(conf["optimizer"]), lambda s: 0.0)
    tmpl = create_train_state(model, opt, jax.random.PRNGKey(0),
                              jnp.zeros((2, 32, 32, 3)), use_ema=False)
    for f in (0, 1):
        a = load_checkpoint(host_paths[f], tmpl)
        b = load_checkpoint(cache_paths[f], tmpl)
        assert int(a.step) == int(b.step)
        for x, y in zip(jax.tree.leaves(a.params), jax.tree.leaves(b.params)):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                       rtol=1e-3, atol=1e-3)
        for x, y in zip(jax.tree.leaves(a.batch_stats),
                        jax.tree.leaves(b.batch_stats)):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                       rtol=5e-2, atol=1e-2)
        assert read_metadata(cache_paths[f])["epoch"] == 1


@pytest.mark.slow
def test_driver_stamps_and_accounting_under_multistep(tmp_path):
    """search_policies with the cache + N=2: flags stamped into the
    result, phase-1 per-fold device-seconds attribution identity holds
    (the PR-2 identity extended to multi-step dispatch), and the final
    policy set matches the host-fed run (same proposals, rewards within
    the documented bound)."""
    from fast_autoaugment_tpu.search.driver import search_policies

    conf = _conf()

    def kwargs(sub):
        d = str(tmp_path / sub)
        os.makedirs(d, exist_ok=True)
        return dict(
            dataroot=d, save_dir=os.path.join(d, "search"), cv_num=2,
            cv_ratio=0.4, num_policy=1, num_op=1, num_search=2, num_top=1,
        )

    r_host = search_policies(conf, **kwargs("host"), device_cache="off")
    r_cache = search_policies(conf, **kwargs("cache"), device_cache="auto",
                              steps_per_dispatch=2, fold_stack="auto")
    assert r_host["device_cache"] == "off"
    assert r_host["steps_per_dispatch"] == 1
    assert r_cache["device_cache"] == "auto"
    assert r_cache["steps_per_dispatch"] == 2
    assert r_cache["final_policy_set"]
    for r in (r_host, r_cache):
        attr = r["device_secs_phase1_per_fold"]
        assert sorted(attr) == ["0", "1"]
        s = sum(attr.values())
        assert 0 < s <= r["device_secs_phase1"] + 1e-6
    # stacked group under multistep still splits its one wall evenly
    assert r_cache["fold_stack"] == 2
    assert r_cache["device_secs_phase1_per_fold"]["0"] == pytest.approx(
        r_cache["device_secs_phase1_per_fold"]["1"])
    t_host = json.load(open(os.path.join(
        str(tmp_path / "host"), "search", "search_trials.json")))
    t_cache = json.load(open(os.path.join(
        str(tmp_path / "cache"), "search", "search_trials.json")))
    for fold in ("0", "1"):
        for (pa, ra), (pb, rb) in zip(t_host[fold], t_cache[fold]):
            assert pa == pb  # same fold-seeded proposal stream
            assert rb == pytest.approx(ra, abs=0.1)


# ----------------------------------------------------------- CLI flags

def test_cli_device_cache_flags():
    from fast_autoaugment_tpu.launch.search_cli import build_parser as search_p
    from fast_autoaugment_tpu.launch.train_cli import build_parser as train_p

    for parser in (search_p(), train_p()):
        args = parser.parse_args(["-c", "x.yaml"])
        assert args.device_cache == "auto"
        assert args.steps_per_dispatch == 1
        args = parser.parse_args(["-c", "x.yaml", "--device-cache", "off",
                                  "--steps-per-dispatch", "32"])
        assert args.device_cache == "off"
        assert args.steps_per_dispatch == 32
        with pytest.raises(SystemExit):
            parser.parse_args(["-c", "x.yaml", "--device-cache", "maybe"])
