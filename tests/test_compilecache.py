"""Persistent compile cache + compile seam (core/compilecache.py).

Fast tests cover the two-case cache placement (variable set / unset),
a second process hitting what the first compiled, seam
classification/delegation and the watchdog warm-allowance coupling —
all host side or one tiny compile.  The slow tests are the acceptance
drills: a warm SECOND PROCESS reports cache hits and a fast first step
on the real train step, a config change goes cold again,
cached-vs-fresh executables train bit-identically, and the exit-77
resume e2e reports a cache hit.
"""

import json
import os
import re
import subprocess
import sys

import jax
import numpy as np
import pytest
from jax.experimental.compilation_cache import compilation_cache as jax_cc

from fast_autoaugment_tpu.core import compilecache as cc
from fast_autoaugment_tpu.core.watchdog import DispatchWatchdog

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _point_jax_cache_at(directory):
    """What starting a process with JAX_COMPILATION_CACHE_DIR=directory
    does, replayed in-process: JAX reads the variable once, at import."""
    jax.config.update("jax_compilation_cache_dir", directory)
    jax_cc.reset_cache()  # drop the handle on the previous directory


@pytest.fixture()
def private_cache(monkeypatch, tmp_path):
    """An empty cache directory placed 'from outside' for one test, and
    the session's placement (tests/conftest.py) restored after it —
    the cache is process-wide state the rest of the suite shares."""
    before = jax.config.jax_compilation_cache_dir
    directory = str(tmp_path / "cache")
    monkeypatch.setenv(cc.ENV_VAR, directory)
    _point_jax_cache_at(directory)
    cc._reset_stats_for_tests()
    cc.configure_compile_cache()
    yield directory
    monkeypatch.undo()  # the session's variable is back before re-arming
    _point_jax_cache_at(before)
    cc._reset_stats_for_tests()
    cc.configure_compile_cache()


@pytest.fixture()
def cache_switched_off():
    """JAX_ENABLE_COMPILATION_CACHE=0, the one off switch."""
    jax.config.update("jax_enable_compilation_cache", False)
    cc._reset_stats_for_tests()
    cc.configure_compile_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    cc._reset_stats_for_tests()
    cc.configure_compile_cache()


# --------------------------------------------------- cache placement


def test_variable_set_places_the_cache_and_no_setter_runs(private_cache,
                                                          monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set: the cache is there, the program
    sets no directory in code, and the stats report that directory."""
    updates = []
    real_update = jax.config.update
    monkeypatch.setattr(
        jax.config, "update",
        lambda key, value: (updates.append(key), real_update(key, value)))
    monkeypatch.setattr(jax_cc, "set_cache_dir",
                        lambda path: updates.append("set_cache_dir"))
    assert cc.configure_compile_cache() == private_cache
    assert "jax_compilation_cache_dir" not in updates
    assert "set_cache_dir" not in updates
    # the compile-time floor is dropped so small modules are cached too
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    stats = cc.compile_cache_stats()
    assert stats["dir"] == private_cache and stats["enabled"] is True
    assert cc.cache_dir() == private_cache


def test_variable_unset_uses_the_fixed_in_checkout_path(monkeypatch,
                                                        tmp_path):
    """No variable: the cache is ON at one fixed path in the checkout —
    never off, never a temp name."""
    assert cc.DEFAULT_CACHE_DIR == os.path.join(REPO, ".jax_cache")
    before = jax.config.jax_compilation_cache_dir
    fixed = str(tmp_path / "fixed")  # keep the test out of the checkout
    monkeypatch.setattr(cc, "DEFAULT_CACHE_DIR", fixed)
    monkeypatch.delenv(cc.ENV_VAR)
    try:
        assert cc.configure_compile_cache() == fixed
        assert jax.config.jax_compilation_cache_dir == fixed
        assert cc.compile_cache_stats()["dir"] == fixed
    finally:
        monkeypatch.undo()
        _point_jax_cache_at(before)
        cc.configure_compile_cache()


_TINY_CHILD = r"""
import json, os
import jax, jax.numpy as jnp
seen = []
real = jax.config.update
jax.config.update = lambda k, v: (seen.append(k), real(k, v))
from fast_autoaugment_tpu.core import compilecache as cc
cc.configure_compile_cache()
fn = cc.seam_jit(lambda x: (x * 3 + 1).sum(), label="tiny")
fn(jnp.ones((8, 8))).block_until_ready()
print(json.dumps({"stats": cc.compile_cache_stats(), "updates": seen}))
"""


def test_second_process_hits_what_the_first_compiled(tmp_path):
    """The cache exists for the NEXT process: same program, same
    directory placed by the variable -> hits, no misses, verdict hit —
    and neither process set a directory in code."""
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    env[cc.ENV_VAR] = str(tmp_path / "cache")

    def child():
        r = subprocess.run([sys.executable, "-c", _TINY_CHILD], env=env,
                           capture_output=True, text=True, timeout=300)
        assert r.returncode == 0, r.stderr[-2000:]
        return json.loads(r.stdout.strip().splitlines()[-1])

    cold, warm = child(), child()
    for rec in (cold, warm):
        assert rec["stats"]["dir"] == env[cc.ENV_VAR]
        assert "jax_compilation_cache_dir" not in rec["updates"]
    assert cold["stats"]["misses"] > 0
    assert cold["stats"]["labels"]["tiny"]["miss"] == 1
    assert warm["stats"]["hits"] > 0 and warm["stats"]["misses"] == 0
    assert warm["stats"]["labels"]["tiny"]["hit"] == 1


# ------------------------------------------------------- seam wrapper


def test_seam_uncached_classification_and_stats(cache_switched_off):
    import jax.numpy as jnp

    fn = cc.seam_jit(lambda x: x * 2 + 1, label="t_uncached")
    out = fn(jnp.ones((4,)))
    assert np.allclose(np.asarray(out), 3.0)
    stats = cc.compile_cache_stats()
    assert stats["enabled"] is False and stats["dir"] is None
    assert stats["labels"]["t_uncached"]["uncached"] == 1
    assert stats["labels"]["t_uncached"]["sec"] > 0
    assert stats["first_step_secs"] >= stats["labels"]["t_uncached"]["sec"]
    # second call is not re-recorded
    fn(jnp.ones((4,)))
    assert cc.compile_cache_stats()["labels"]["t_uncached"]["uncached"] == 1


def test_seam_delegates_lower_and_attributes():
    import jax.numpy as jnp

    fn = cc.seam_jit(lambda x: x + 1, label="t_deleg")
    # .lower is delegated to the wrapped jit object (AOT lowering)
    compiled = fn.lower(jnp.ones((2,))).compile()
    assert np.allclose(np.asarray(compiled(jnp.ones((2,)))), 2.0)
    # census probes _cache_size through the wrapper (attribute
    # delegation); attaching attributes works too (tta trace counter)
    fn._faa_trace_count = lambda: 7
    assert fn._faa_trace_count() == 7


def test_seam_hit_miss_in_process(private_cache):
    """With an empty cache, compile a fn (miss), re-jit an IDENTICAL but
    distinct fn (hit: a distinct function identity bypasses jax's
    in-memory tracing caches, so the compile reaches the persistent
    layer and deserializes — the same path a fresh process takes)."""
    import jax.numpy as jnp

    def make_body():
        def body(x):
            return (x * 3).sum() + 1
        return body

    a = cc.seam_jit(make_body(), label="t_cold")
    a(jnp.ones((8, 8)))
    stats = cc.compile_cache_stats()
    assert stats["misses"] > 0
    assert stats["labels"]["t_cold"]["miss"] == 1

    b = cc.seam_jit(make_body(), label="t_warm")
    b(jnp.ones((8, 8)))
    stats = cc.compile_cache_stats()
    assert stats["hits"] > 0
    assert stats["labels"]["t_warm"]["hit"] == 1
    # at least one persistent entry landed on disk
    assert any(f.endswith("-cache")
               for f in os.listdir(cc.cache_dir()))


# --------------------------------------- watchdog warm-allowance coupling


def test_watchdog_first_call_blind_window_when_cold():
    wd = DispatchWatchdog("auto", compile_allowance=600.0)
    assert wd.deadline("train_dispatch") == 600.0


def test_watchdog_shrinks_first_call_when_process_warm(monkeypatch):
    wd = DispatchWatchdog("auto", compile_allowance=600.0,
                          warm_allowance=45.0)
    monkeypatch.setattr(cc, "process_is_warm", lambda: True)
    # the seam has proven the cache warm: no blind 600s window
    assert wd.deadline("train_dispatch") == 45.0
    # steady state is untouched
    wd.observe("train_dispatch", 2.0)
    assert wd.deadline("train_dispatch") == pytest.approx(40.0)


def test_watchdog_mark_compile_warm_fixed_mode():
    wd = DispatchWatchdog(5.0, compile_allowance=600.0)
    assert wd.deadline("serve_exact_b8") == 600.0
    wd.mark_compile_warm("serve_exact_b8")
    # AOT-loaded executable: first dispatch gets the NORMAL deadline
    assert wd.deadline("serve_exact_b8") == 5.0
    assert "serve_exact_b8" in wd.stats()["warm_labels"]


def test_watchdog_warm_floor_respects_min_deadline():
    wd = DispatchWatchdog("auto", warm_allowance=1.0, min_deadline=10.0)
    wd.mark_compile_warm("d")
    assert wd.deadline("d") == 10.0


# -------------------------------------------------- subprocess drills

_CHILD = r"""
import json, os, sys, time
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import jax, jax.numpy as jnp, numpy as np
from fast_autoaugment_tpu.core.compilecache import (
    compile_cache_stats, configure_compile_cache)
configure_compile_cache()  # placed by JAX_COMPILATION_CACHE_DIR
from fast_autoaugment_tpu.models import get_model
from fast_autoaugment_tpu.ops.optim import build_optimizer
from fast_autoaugment_tpu.train.steps import create_train_state, make_train_step
width = int(os.environ.get("T_WIDTH", "1"))
model = get_model({"type": "wresnet10_%d" % width}, 10)
opt = build_optimizer({"type": "sgd", "decay": 2e-4, "clip": 5.0,
                       "momentum": 0.9, "nesterov": True}, lambda s: 0.05)
rng = jax.random.PRNGKey(0)
sample = jnp.zeros((2, 8, 8, 3), jnp.float32)
state = create_train_state(model, opt, rng, sample, use_ema=False)
step = make_train_step(model, opt, num_classes=10, cutout_length=0,
                       use_policy=False)
host = np.random.default_rng(0)
x = jnp.asarray(host.integers(0, 256, (4, 8, 8, 3), dtype=np.uint8))
y = jnp.asarray(host.integers(0, 10, (4,), np.int32))
pol = jnp.zeros((1, 1, 3), jnp.float32)
t0 = time.perf_counter()
state, m = step(state, x, y, pol, rng)
jax.block_until_ready(state.params)
print(json.dumps({"first_step_sec": time.perf_counter() - t0,
                  "stats": compile_cache_stats()}))
"""


def _run_child(cache_dir, width=1):
    env = dict(os.environ)
    env[cc.ENV_VAR] = str(cache_dir)
    env["JAX_PLATFORMS"] = "cpu"
    env["T_WIDTH"] = str(width)
    r = subprocess.run([sys.executable, "-c", _CHILD], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


@pytest.mark.slow
def test_cache_key_stability_warm_second_process_cold_after_change(tmp_path):
    """The tentpole acceptance shape: same config -> the second process
    is WARM (hits, no misses, faster first step); a config change ->
    cold again (misses)."""
    cache = tmp_path / "cache"
    cold = _run_child(cache)
    assert cold["stats"]["misses"] > 0
    assert cold["stats"]["labels"]["train_step"]["miss"] == 1

    warm = _run_child(cache)
    assert warm["stats"]["hits"] > 0
    assert warm["stats"]["misses"] == 0
    assert warm["stats"]["labels"]["train_step"]["hit"] == 1
    # the whole point: the warm first step costs a fraction of cold
    assert warm["first_step_sec"] < cold["first_step_sec"]

    changed = _run_child(cache, width=2)  # different model width
    assert changed["stats"]["misses"] > 0  # cold for the new program


@pytest.mark.slow
def test_cached_vs_fresh_executables_bitwise(tmp_path):
    """Seeded equivalence across the cache boundary: a COLD process and
    a WARM process (deserialized executables) produce bit-identical
    training results — caching changes where executables come from,
    never what they compute."""
    conf = (
        "model:\n  type: wresnet10_1\ndataset: synthetic\naug: default\n"
        "cutout: 0\nbatch: 8\nepoch: 1\nlr: 0.05\n"
        "lr_schedule:\n  type: cosine\n"
        "optimizer:\n  type: sgd\n  decay: 0.0001\n  momentum: 0.9\n"
        "  nesterov: true\n")
    conf_yaml = tmp_path / "conf.yaml"
    conf_yaml.write_text(conf)

    def train(save, cache_dir):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env[cc.ENV_VAR] = str(cache_dir)
        r = subprocess.run(
            [sys.executable, "-m", "fast_autoaugment_tpu.launch.train_cli",
             "-c", str(conf_yaml), "--dataroot", str(tmp_path),
             "--save", save, "--cv-ratio", "0.4",
             "--evaluation-interval", "1"],
            env=env, capture_output=True, text=True, timeout=900)
        assert r.returncode == 0, r.stderr[-2000:]
        return r

    import hashlib

    def digest(path):
        with open(path, "rb") as fh:
            return hashlib.sha256(fh.read()).hexdigest()

    ck_cache = tmp_path / "ck_cache"
    train(str(tmp_path / "a.msgpack"), ck_cache)   # cold
    r2 = train(str(tmp_path / "b.msgpack"), ck_cache)  # warm
    assert re.search(r"compile cache: dir=\S+ hits=[1-9]", r2.stderr), \
        r2.stderr[-2000:]
    assert digest(tmp_path / "a.msgpack") == digest(tmp_path / "b.msgpack")


@pytest.mark.slow
def test_exit77_resume_reports_cache_hit(tmp_path):
    """The resilience coupling end-to-end: a SIGTERMed CLI trainer
    exits 77 (checkpointed), and the RESUMED process — sharing the
    compile-cache dir — reports cache hits: the resume reached its
    first step without re-paying the compile tax."""
    conf_yaml = tmp_path / "conf.yaml"
    conf_yaml.write_text(
        "model:\n  type: wresnet10_1\ndataset: synthetic\naug: default\n"
        "cutout: 0\nbatch: 8\nepoch: 2\nlr: 0.05\n"
        "lr_schedule:\n  type: cosine\n"
        "optimizer:\n  type: sgd\n  decay: 0.0001\n  momentum: 0.9\n"
        "  nesterov: true\n")
    cache = tmp_path / "cache"

    def run(fault=None):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env.pop("FAA_FAULT", None)
        env[cc.ENV_VAR] = str(cache)
        if fault:
            env["FAA_FAULT"] = fault
        return subprocess.run(
            [sys.executable, "-m", "fast_autoaugment_tpu.launch.train_cli",
             "-c", str(conf_yaml), "--dataroot", str(tmp_path),
             "--save", str(tmp_path / "ck.msgpack"), "--cv-ratio", "0.4",
             "--evaluation-interval", "1"],
            env=env, capture_output=True, text=True, timeout=900)

    r = run(fault="sigterm@step=2")
    assert r.returncode == 77, (r.returncode, r.stderr[-2000:])

    r2 = run()
    assert r2.returncode == 0, r2.stderr[-2000:]
    assert "resumed" in r2.stderr
    m = re.search(r"compile cache: dir=\S+ hits=(\d+) misses=(\d+)",
                  r2.stderr)
    assert m, r2.stderr[-2000:]
    assert int(m.group(1)) > 0, "resumed process reported no cache hits"


# ---------------------------------------------------------------------------
# the first call's own stretch of the interpreter's frame stack (_with_room)
# ---------------------------------------------------------------------------


def _faults_by_depth(call, depths, calls=1000):
    """Minor page faults of a loop of `calls` small calls made `depth`
    plain frames down, for each depth, entered through `call(fn, depth)`."""
    import resource

    def faults():
        return resource.getrusage(resource.RUSAGE_SELF).ru_minflt

    # a callee whose frame is larger than `down`'s: the scan over depths
    # moves the loop's frame along a chunk in steps of `down`'s frame, and
    # the loop thrashes where less than the callee's frame is left
    leaf = eval("lambda " + ", ".join(f"a{i}=0" for i in range(40)) + ": a0")

    def hot():
        before = faults()
        for _ in range(calls):
            leaf()
        return faults() - before

    def down(depth):
        return hot() if depth == 0 else down(depth - 1)

    return {depth: call(down, depth) for depth in depths}


def test_with_room_passes_the_call_through():
    assert cc._with_room(lambda a, b=0: (a, b), 1, b=2) == (1, 2)
    with pytest.raises(KeyError, match="gone"):
        cc._with_room({}.__getitem__, "gone")


def test_with_room_keeps_a_loop_off_the_end_of_a_stack_chunk():
    """CPython keeps frames in 16 KiB chunks and unmaps a chunk when its
    first frame returns: at some depth a loop of calls maps and unmaps
    one per call (a page fault each, at least).  Under `_with_room` no
    depth does."""
    depths, calls = range(0, 320), 1000
    plain = _faults_by_depth(lambda fn, depth: fn(depth), depths, calls)
    if max(plain.values()) < calls:
        pytest.skip("this interpreter maps no stack chunk per call at any "
                    f"depth to {depths[-1]}: nothing for _with_room to keep off")
    roomy = _faults_by_depth(cc._with_room, depths, calls)
    assert max(roomy.values()) < calls // 4, (
        max(plain.values()), {d: f for d, f in roomy.items() if f >= calls // 4})
