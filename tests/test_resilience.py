"""Fault-injection matrix for the resilience subsystem
(docs/RESILIENCE.md): every recovery path — checksummed restore
chains, preemption-safe shutdown + exit-77 resume, divergence
recovery, phase-2 trial quarantine, fleet host retries — is driven
DETERMINISTICALLY through ``FAA_FAULT`` (``utils/faultinject.py``)
rather than trusted on faith.  Defaults-equivalence (all resilience
knobs off => bit-for-bit the historical run) rides on the existing
checkpoint-equivalence harness plus the chain-depth pin here."""

import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

import jax

from fast_autoaugment_tpu.core import resilience
from fast_autoaugment_tpu.core.checkpoint import (
    CheckpointCorruptError,
    chain_paths,
    checkpoint_exists,
    load_checkpoint,
    load_checkpoint_chain,
    read_metadata,
    save_checkpoint,
)
from fast_autoaugment_tpu.core.config import Config
from fast_autoaugment_tpu.utils import faultinject


@pytest.fixture(autouse=True)
def _clean_fault_state():
    """Every test starts (and ends) with no fault plan and a clear
    preemption flag — faultinject state is process-wide."""
    os.environ.pop("FAA_FAULT", None)
    faultinject.reset()
    resilience.clear_preemption()
    yield
    os.environ.pop("FAA_FAULT", None)
    faultinject.reset()
    resilience.clear_preemption()


def _conf(**over):
    base = {
        "model": {"type": "wresnet10_1"},
        "dataset": "synthetic",
        "aug": "default",
        "cutout": 0,
        "batch": 8,
        "epoch": 2,
        "lr": 0.05,
        "lr_schedule": {"type": "cosine"},
        "optimizer": {"type": "sgd", "decay": 1e-4, "momentum": 0.9,
                      "nesterov": True},
    }
    base.update(over)
    return Config(base)


# ------------------------------------------------- FAA_FAULT grammar

def test_parse_fault_spec_grammar():
    faults = faultinject.parse_fault_spec(
        "nan_loss@step=7;sigterm@step=12;torn_ckpt@save=3;"
        "io_error@p=0.1,seed=4; trial_error@trial=2")
    kinds = [f["kind"] for f in faults]
    assert kinds == ["nan_loss", "sigterm", "torn_ckpt", "io_error",
                     "trial_error"]
    assert faults[0]["step"] == 7
    assert faults[2]["save"] == 3
    assert faults[3]["p"] == pytest.approx(0.1)
    assert faults[3]["seed"] == 4
    assert faults[4]["trial"] == 2
    assert faultinject.parse_fault_spec("") == []


@pytest.mark.parametrize("bad", [
    "explode@step=1",           # unknown kind
    "nan_loss",                 # missing @args
    "nan_loss@step",            # malformed kv
    "nan_loss@save=1",          # wrong key for kind
    "io_error@p=1.5",           # p outside [0, 1]
    "sigterm@",                 # missing required key
])
def test_parse_fault_spec_rejects(bad):
    with pytest.raises(ValueError):
        faultinject.parse_fault_spec(bad)


def test_fault_plan_fires_once_and_caches_by_env_value():
    os.environ["FAA_FAULT"] = "nan_loss@step=5"
    plan = faultinject.active_plan()
    assert plan is not None
    assert not plan.nan_loss_in(0, 5)       # [0, 5) misses step 5
    assert plan.nan_loss_in(5, 10)          # fires
    assert not plan.nan_loss_in(5, 10)      # consumed
    assert faultinject.active_plan() is plan  # same env -> same state
    os.environ["FAA_FAULT"] = ""
    assert faultinject.active_plan() is None


def test_preemption_flag_roundtrip():
    assert not resilience.preemption_requested()
    resilience.request_preemption()
    assert resilience.preemption_requested()
    resilience.clear_preemption()
    assert not resilience.preemption_requested()
    assert resilience.PREEMPTED_EXIT_CODE == 77
    assert resilience.PreemptedError.exit_code == 77


def test_signal_handler_sets_flag_only():
    assert resilience.install_signal_handlers()
    os.kill(os.getpid(), signal.SIGUSR1)
    # the handler only sets the flag; nothing raised, nothing exited
    assert resilience.preemption_requested()


# ------------------------------------------- restore chain integrity

def _toy_state(v: float):
    return {"w": np.full((4, 4), v, np.float32), "b": np.float32(v)}


def test_checkpoint_digest_and_corruption_detected(tmp_path):
    path = str(tmp_path / "ck.msgpack")
    save_checkpoint(path, _toy_state(1.0), {"epoch": 1})
    meta = read_metadata(path)
    assert meta["epoch"] == 1 and len(meta["digest"]) == 64
    assert meta["nbytes"] == os.path.getsize(path)
    out = load_checkpoint(path, _toy_state(0.0))
    assert float(out["b"]) == 1.0

    # silent bit-rot: same size, flipped byte -> typed corruption error
    blob = bytearray(open(path, "rb").read())
    blob[len(blob) // 2] ^= 0xFF
    with open(path, "wb") as fh:  # robust: allow — test corrupts on purpose
        fh.write(bytes(blob))
    with pytest.raises(CheckpointCorruptError):
        load_checkpoint(path, _toy_state(0.0))
    # torn write: truncated payload -> size mismatch, same typed error
    with open(path, "wb") as fh:  # robust: allow — test tears on purpose
        fh.write(bytes(blob[: len(blob) // 2]))
    with pytest.raises(CheckpointCorruptError):
        load_checkpoint(path, _toy_state(0.0))


def test_restore_chain_rotation_and_walk(tmp_path):
    path = str(tmp_path / "ck.msgpack")
    for i, v in enumerate([1.0, 2.0, 3.0]):
        save_checkpoint(path, _toy_state(v), {"epoch": i + 1}, keep=2)
    links = chain_paths(path, keep=2)
    assert links == [path, path + ".prev"]
    assert read_metadata(path)["epoch"] == 3
    assert read_metadata(path + ".prev")["epoch"] == 2
    assert not os.path.exists(path + ".prev2")  # bounded depth

    # corrupt the newest link: the chain walk recovers the predecessor,
    # reporting which link it used
    with open(path, "wb") as fh:  # robust: allow — test corrupts on purpose
        fh.write(b"garbage")
    got = load_checkpoint_chain(path, _toy_state(0.0), keep=2)
    assert got is not None
    state, meta, used = got
    assert used == path + ".prev"
    assert meta["epoch"] == 2 and float(state["b"]) == 2.0

    # accept predicate: reject everything -> None
    assert load_checkpoint_chain(path, _toy_state(0.0), keep=2,
                                 accept=lambda m: False) is None


def test_ckpt_keep_one_is_prechain_overwrite(tmp_path):
    path = str(tmp_path / "ck.msgpack")
    save_checkpoint(path, _toy_state(1.0), {"epoch": 1}, keep=1)
    save_checkpoint(path, _toy_state(2.0), {"epoch": 2}, keep=1)
    assert not os.path.exists(path + ".prev")
    assert read_metadata(path)["epoch"] == 2


def test_checkpoint_exists_rejects_zero_byte_and_orphan(tmp_path):
    path = str(tmp_path / "ck.msgpack")
    # zero-byte payload left by a crashed pre-atomic-write process
    open(path, "wb").close()  # robust: allow — simulating the crash artifact
    with open(path + ".meta.json", "w") as fh:  # robust: allow — ditto
        json.dump({"epoch": 1}, fh)
    assert not checkpoint_exists(path)
    # nonzero payload but no/torn sidecar
    with open(path, "wb") as fh:  # robust: allow — ditto
        fh.write(b"x" * 64)
    os.remove(path + ".meta.json")
    assert not checkpoint_exists(path)
    with open(path + ".meta.json", "w") as fh:  # robust: allow — ditto
        fh.write("{torn")
    assert not checkpoint_exists(path)
    # intact pair
    save_checkpoint(path, _toy_state(1.0), {"epoch": 1})
    assert checkpoint_exists(path)


def test_read_metadata_absorbs_oserror(tmp_path):
    # sidecar path resolves to a directory -> OSError, not a crash
    path = str(tmp_path / "ck.msgpack")
    os.makedirs(path + ".meta.json")
    assert read_metadata(path) is None


# ------------------------------------ injected checkpoint-write faults

def test_torn_ckpt_injection_walks_chain(tmp_path):
    path = str(tmp_path / "ck.msgpack")
    # saves are counted while the plan is active (1-based)
    os.environ["FAA_FAULT"] = "torn_ckpt@save=2"
    faultinject.reset()
    save_checkpoint(path, _toy_state(1.0), {"epoch": 1})
    save_checkpoint(path, _toy_state(2.0), {"epoch": 2})  # torn mid-write
    # the live link is torn (full-payload digest over half the bytes)
    with pytest.raises(CheckpointCorruptError):
        load_checkpoint(path, _toy_state(0.0))
    state, meta, used = load_checkpoint_chain(path, _toy_state(0.0))
    assert used == path + ".prev" and meta["epoch"] == 1
    assert float(state["b"]) == 1.0  # one torn file cost one epoch


def test_corrupt_ckpt_injection_detected(tmp_path):
    path = str(tmp_path / "ck.msgpack")
    os.environ["FAA_FAULT"] = "corrupt_ckpt@save=2"
    faultinject.reset()
    save_checkpoint(path, _toy_state(1.0), {"epoch": 1})
    save_checkpoint(path, _toy_state(2.0), {"epoch": 2})  # bit-rot
    with pytest.raises(CheckpointCorruptError):
        load_checkpoint(path, _toy_state(0.0))
    _state, meta, used = load_checkpoint_chain(path, _toy_state(0.0))
    assert used == path + ".prev" and meta["epoch"] == 1


def test_io_error_injection_chain_exhaustion(tmp_path):
    path = str(tmp_path / "ck.msgpack")
    save_checkpoint(path, _toy_state(1.0), {"epoch": 1})
    os.environ["FAA_FAULT"] = "io_error@p=1.0,seed=0"
    faultinject.reset()
    with pytest.raises(OSError):
        load_checkpoint(path, _toy_state(0.0))
    # every link unreadable -> the walk comes up empty, loudly, instead
    # of crashing the caller
    assert load_checkpoint_chain(path, _toy_state(0.0)) is None
    os.environ.pop("FAA_FAULT")
    faultinject.reset()
    assert load_checkpoint_chain(path, _toy_state(0.0)) is not None


# --------------------------------------------- fleet host supervision

def _fake_remote(script_by_host, tmp_path):
    """Substitute a local bash script for ssh (per host)."""
    def _argv(host, wire):
        return ["bash", "-c", script_by_host[host]]
    return _argv


def test_fleet_retries_preempted_host_then_succeeds(tmp_path, monkeypatch):
    from fast_autoaugment_tpu.launch import fleet as fleet_mod

    counter = tmp_path / "attempts"
    script = (f"n=$(cat {counter} 2>/dev/null || echo 0); n=$((n+1)); "
              f"echo $n > {counter}; [ $n -ge 3 ] && exit 0 || exit 77")
    monkeypatch.setattr(fleet_mod, "_remote_argv",
                        _fake_remote({"a": script}, tmp_path))
    code = fleet_mod.launch_fleet(["a"], ["true"], "x:1", host_retries=2,
                                  retry_backoff=0.01)
    assert code == 0  # two preempted exits (77), third attempt clean
    assert counter.read_text().strip() == "3"


def test_fleet_out_of_retries_propagates_first_genuine_failure(
        tmp_path, monkeypatch):
    from fast_autoaugment_tpu.launch import fleet as fleet_mod

    # host a (waited FIRST) hangs and dies from the teardown kill; host
    # b fails genuinely with 5.  The old `worst = worst or code` wait
    # loop reported a's kill signal; the supervisor must report b's 5.
    scripts = {"a": "sleep 30; exit 0", "b": "sleep 0.1; exit 5"}
    monkeypatch.setattr(fleet_mod, "_remote_argv",
                        _fake_remote(scripts, tmp_path))
    t0 = time.time()
    code = fleet_mod.launch_fleet(["a", "b"], ["true"], "x:1",
                                  host_retries=0, retry_backoff=0.01)
    assert code == 5
    assert time.time() - t0 < 20  # teardown, not the 30 s sleep


def test_fleet_zero_retries_tears_down_on_77(tmp_path, monkeypatch):
    from fast_autoaugment_tpu.launch import fleet as fleet_mod

    monkeypatch.setattr(fleet_mod, "_remote_argv",
                        _fake_remote({"a": "exit 77"}, tmp_path))
    code = fleet_mod.launch_fleet(["a"], ["true"], "x:1", host_retries=0)
    # with no retry budget the preempted code propagates — the OUTER
    # supervisor (or operator) still sees "resume me"
    assert code == 77


def test_fleet_backoff_is_exponential(tmp_path, monkeypatch):
    from fast_autoaugment_tpu.launch import fleet as fleet_mod

    stamps = tmp_path / "stamps"
    script = f"date +%s.%N >> {stamps}; exit 1"
    monkeypatch.setattr(fleet_mod, "_remote_argv",
                        _fake_remote({"a": script}, tmp_path))
    code = fleet_mod.launch_fleet(["a"], ["true"], "x:1", host_retries=2,
                                  retry_backoff=0.2)
    assert code == 1
    times = [float(x) for x in stamps.read_text().split()]
    assert len(times) == 3  # 1 launch + 2 retries
    gap1, gap2 = times[1] - times[0], times[2] - times[1]
    assert gap1 >= 0.2 and gap2 >= 0.4  # 0.2 * 2^attempt


def test_fleet_cli_flags_parse():
    from fast_autoaugment_tpu.launch.fleet import main

    with pytest.raises(SystemExit):  # no command after flags
        main(["--hosts", "2", "--host-retries", "3", "--retry-backoff",
              "0.5"])


# ------------------------------------------ trainer fault matrix (slow)

_TRAIN_KW = dict(test_ratio=0.4, cv_fold=0, metric="last", seed=0,
                 evaluation_interval=1)


def _final_digest(path: str) -> str:
    meta = read_metadata(path)
    assert meta and "digest" in meta
    return meta["digest"]


@pytest.mark.slow
def test_sigterm_preemption_checkpoints_and_resumes_bit_identical(tmp_path):
    """The flagship matrix case: SIGTERM mid-epoch-2 (injected at the
    step seam) -> checkpoint at the dispatch boundary with
    ``preempted: true`` + the exact position -> PreemptedError (exit-77
    contract) -> the rerun fast-forwards and lands a checkpoint
    BIT-IDENTICAL to an uninterrupted run."""
    from fast_autoaugment_tpu.core.resilience import PreemptedError
    from fast_autoaugment_tpu.train.trainer import train_and_eval

    tmp = str(tmp_path)
    conf = _conf()  # 512 synthetic examples, 0.4 ratio -> 4 steps/epoch
    full = f"{tmp}/full.msgpack"
    train_and_eval(conf, tmp, save_path=full, **_TRAIN_KW)

    part = f"{tmp}/part.msgpack"
    os.environ["FAA_FAULT"] = "sigterm@step=6"  # epoch 2, position 2/4
    faultinject.reset()
    with pytest.raises(PreemptedError):
        train_and_eval(conf, tmp, save_path=part, **_TRAIN_KW)
    meta = read_metadata(part)
    assert meta["preempted"] is True
    assert meta["in_epoch"] == {
        "epoch": 2, "pos": 2, "sums": meta["in_epoch"]["sums"],
        "retries": 0}
    assert meta["epoch"] == 1  # last COMPLETED epoch

    os.environ.pop("FAA_FAULT")
    faultinject.reset()
    resilience.clear_preemption()
    r = train_and_eval(conf, tmp, save_path=part, **_TRAIN_KW)
    assert r["epoch"] == 2
    assert _final_digest(part) == _final_digest(full)
    # the resumed epoch's reported metrics continue the same f32 chain
    m_full, m_part = read_metadata(full)["metrics"], read_metadata(part)["metrics"]
    for k in ("loss_train", "top1_train", "top1_test"):
        assert m_full[k] == m_part[k], k


@pytest.mark.slow
def test_sigterm_on_host_path_preempts_at_dispatch_boundary(tmp_path):
    """The host-fed path gives the device-cache path's guarantee (PR 32;
    the fast form is tests/test_hostfed_preemption.py): SIGTERM mid-epoch-1
    -> a checkpoint with the exact position -> the rerun skips the batches
    already trained and lands a checkpoint bit-identical to an unbroken
    run's."""
    from fast_autoaugment_tpu.core.resilience import PreemptedError
    from fast_autoaugment_tpu.train.trainer import train_and_eval

    tmp = str(tmp_path)
    conf = _conf()
    part = f"{tmp}/host.msgpack"
    os.environ["FAA_FAULT"] = "sigterm@step=2"  # mid-epoch-1
    faultinject.reset()
    with pytest.raises(PreemptedError):
        train_and_eval(conf, tmp, save_path=part, device_cache="off",
                       **_TRAIN_KW)
    meta = read_metadata(part)
    assert meta["preempted"] is True and meta["epoch"] == 0
    assert meta["step"] == 2
    assert (meta["in_epoch"]["epoch"], meta["in_epoch"]["pos"]) == (1, 2)

    os.environ.pop("FAA_FAULT")
    faultinject.reset()
    resilience.clear_preemption()
    r = train_and_eval(conf, tmp, save_path=part, device_cache="off",
                       **_TRAIN_KW)
    assert r["epoch"] == 2
    full = f"{tmp}/host_full.msgpack"
    train_and_eval(conf, tmp, save_path=full, device_cache="off",
                   **_TRAIN_KW)
    assert _final_digest(part) == _final_digest(full)


@pytest.mark.slow
def test_nan_divergence_rollback_retry_then_succeed(tmp_path):
    """NaN at an epoch-2 step: with --divergence-retries 1 the trainer
    rolls back to the epoch-1 checkpoint, replays with retry-folded
    randomness (the consumed injection does not re-fire) and completes;
    with the default 0 it raises exactly as before."""
    from fast_autoaugment_tpu.train.trainer import train_and_eval

    tmp = str(tmp_path)
    conf = _conf()
    os.environ["FAA_FAULT"] = "nan_loss@step=5"
    faultinject.reset()
    with pytest.raises(RuntimeError, match="diverged"):
        train_and_eval(conf, tmp, save_path=f"{tmp}/raise.msgpack",
                       **_TRAIN_KW)

    os.environ["FAA_FAULT"] = "nan_loss@step=5"
    faultinject.reset()
    r = train_and_eval(conf, tmp, save_path=f"{tmp}/retry.msgpack",
                       divergence_retries=1, **_TRAIN_KW)
    assert r["epoch"] == 2
    assert np.isfinite(r["loss_train"])


@pytest.mark.slow
def test_nan_without_checkpoint_still_raises(tmp_path):
    from fast_autoaugment_tpu.train.trainer import train_and_eval

    os.environ["FAA_FAULT"] = "nan_loss@step=1"  # epoch 1: nothing saved yet
    faultinject.reset()
    with pytest.raises(RuntimeError, match="diverged"):
        train_and_eval(_conf(), str(tmp_path),
                       save_path=f"{tmp_path}/x.msgpack",
                       divergence_retries=3, **_TRAIN_KW)


@pytest.mark.slow
def test_torn_checkpoint_resume_recovers_from_chain(tmp_path):
    """A torn WRITE of the epoch-2 checkpoint (crash mid-save) costs
    exactly one epoch on resume: the chain walks back to epoch 1 and
    the rerun reproduces the uninterrupted final checkpoint."""
    from fast_autoaugment_tpu.train.trainer import train_and_eval

    tmp = str(tmp_path)
    conf = _conf()
    full = f"{tmp}/full.msgpack"
    train_and_eval(conf, tmp, save_path=full, **_TRAIN_KW)

    part = f"{tmp}/torn.msgpack"
    os.environ["FAA_FAULT"] = "torn_ckpt@save=2"  # the epoch-2 save tears
    faultinject.reset()
    train_and_eval(conf, tmp, save_path=part, **_TRAIN_KW)
    os.environ.pop("FAA_FAULT")
    faultinject.reset()
    # the live link is corrupt; resume walks to epoch 1 and replays
    r = train_and_eval(conf, tmp, save_path=part, **_TRAIN_KW)
    assert r["epoch"] == 2
    assert _final_digest(part) == _final_digest(full)


@pytest.mark.slow
def test_ckpt_keep_default_chain_matches_keep1_bitwise(tmp_path):
    """Defaults-equivalence: the rollback chain only ADDS .prev files —
    the live checkpoint trajectory is bit-for-bit the keep=1
    (pre-chain) behavior."""
    from fast_autoaugment_tpu.train.trainer import train_and_eval

    tmp = str(tmp_path)
    conf = _conf()
    a, b = f"{tmp}/keep2.msgpack", f"{tmp}/keep1.msgpack"
    train_and_eval(conf, tmp, save_path=a, ckpt_keep=2, **_TRAIN_KW)
    train_and_eval(conf, tmp, save_path=b, ckpt_keep=1, **_TRAIN_KW)
    assert _final_digest(a) == _final_digest(b)
    assert os.path.exists(a + ".prev") and not os.path.exists(b + ".prev")


@pytest.mark.slow
def test_stacked_preemption_and_resume_bit_identical(tmp_path, devices8):
    """Fold-stacked phase 1 under SIGTERM at a dispatch boundary: every
    active fold checkpoints its slice with the shared mid-epoch
    position; the rerun fast-forwards and matches the uninterrupted
    stacked run bit-for-bit per fold."""
    from fast_autoaugment_tpu.core.resilience import PreemptedError
    from fast_autoaugment_tpu.parallel.mesh import make_fold_mesh
    from fast_autoaugment_tpu.train.trainer import train_folds_stacked

    tmp = str(tmp_path)
    conf = _conf()
    mesh = make_fold_mesh(2, devices=jax.devices()[:8])
    kw = dict(cv_ratio=0.4, folds=[0, 1], seed=0, evaluation_interval=1,
              mesh=mesh)
    full_paths = [f"{tmp}/full_f{k}.msgpack" for k in (0, 1)]
    train_folds_stacked(conf, tmp, save_paths=full_paths, **kw)

    part_paths = [f"{tmp}/part_f{k}.msgpack" for k in (0, 1)]
    os.environ["FAA_FAULT"] = "sigterm@step=6"
    faultinject.reset()
    with pytest.raises(PreemptedError):
        train_folds_stacked(conf, tmp, save_paths=part_paths, **kw)
    for p in part_paths:
        meta = read_metadata(p)
        assert meta["preempted"] is True and "in_epoch" in meta

    os.environ.pop("FAA_FAULT")
    faultinject.reset()
    resilience.clear_preemption()
    res = train_folds_stacked(conf, tmp, save_paths=part_paths, **kw)
    assert res[0]["epoch"] == res[1]["epoch"] == 2
    for fp, pp in zip(full_paths, part_paths):
        assert _final_digest(fp) == _final_digest(pp)


# ------------------------------------------ phase-2 trial quarantine

@pytest.mark.slow
def test_search_quarantines_failed_trial(tmp_path):
    """An injected TTA failure at trial 1 must not kill the search: the
    trial is told to TPE as the worst observed reward, the trial log
    carries the failure record, search_result stamps
    quarantined_trials, and the quarantined trial never ranks."""
    from fast_autoaugment_tpu.search.driver import search_policies

    save = str(tmp_path / "search")
    kwargs = dict(
        dataroot=str(tmp_path), save_dir=save, cv_num=1, cv_ratio=0.4,
        num_policy=1, num_op=1, num_search=4, num_top=2)
    os.environ["FAA_FAULT"] = "trial_error@trial=1"
    faultinject.reset()
    result = search_policies(_conf(epoch=1), **kwargs)
    trials = json.load(open(os.path.join(save, "search_trials.json")))
    assert len(trials["0"]) == 4  # the failed trial still spent budget
    q_entries = [t for t in trials["0"] if len(t) >= 3]
    assert len(q_entries) == 1
    assert q_entries[0][2]["quarantined"] is True
    assert "injected trial_error" in q_entries[0][2]["error"]
    # pessimistic reward: the worst observation at quarantine time —
    # trial 0 was the only one told, so its reward is the liar value
    assert q_entries[0][1] == pytest.approx(trials["0"][0][1])
    assert result["quarantined_trials"] == [
        {"fold": 0, "trial": 1,
         "error": q_entries[0][2]["error"]}]
    assert result["num_quarantined_trials"] == 1
    assert result["final_policy_set"]  # the search completed and ranked

    # resume: the quarantined entry is NOT re-evaluated and the stamp
    # survives from the persisted log
    os.environ.pop("FAA_FAULT")
    faultinject.reset()
    result2 = search_policies(_conf(epoch=1), **kwargs)
    assert result2["num_quarantined_trials"] == 1
    trials2 = json.load(open(os.path.join(save, "search_trials.json")))
    assert trials2 == trials


# --------------------------------- resume under fire (SIGKILL, subprocess)

@pytest.mark.slow
def test_sigkill_resume_from_last_dispatch_boundary(tmp_path):
    """The unannounced-death case: a subprocess trainer is SIGKILLed
    mid-epoch (faultinject sigkill@step) while --ckpt-every-dispatch 1
    snapshots every boundary; the rerun resumes from the LAST dispatch
    boundary and the completed checkpoint is bit-identical to an
    uninterrupted run."""
    tmp = str(tmp_path)
    conf_yaml = tmp_path / "conf.yaml"
    conf_yaml.write_text(
        "model:\n  type: wresnet10_1\ndataset: synthetic\naug: default\n"
        "cutout: 0\nbatch: 8\nepoch: 2\nlr: 0.05\n"
        "lr_schedule:\n  type: cosine\n"
        "optimizer:\n  type: sgd\n  decay: 0.0001\n  momentum: 0.9\n"
        "  nesterov: true\n")

    def run(save, fault=None, extra=()):
        env = dict(os.environ)
        env.pop("FAA_FAULT", None)
        if fault:
            env["FAA_FAULT"] = fault
        return subprocess.run(
            [sys.executable, "-m", "fast_autoaugment_tpu.launch.train_cli",
             "-c", str(conf_yaml), "--dataroot", tmp, "--save", save,
             "--cv-ratio", "0.4", "--evaluation-interval", "1",
             *extra],
            env=env, capture_output=True, text=True, timeout=900)

    full = f"{tmp}/full.msgpack"
    r = run(full)
    assert r.returncode == 0, r.stderr[-2000:]

    part = f"{tmp}/part.msgpack"
    r = run(part, fault="sigkill@step=6",
            extra=("--ckpt-every-dispatch", "1"))
    assert r.returncode == -signal.SIGKILL  # died without ceremony
    meta = read_metadata(part)
    assert meta is not None and "in_epoch" in meta
    assert meta["in_epoch"]["epoch"] == 2  # a mid-epoch-2 boundary

    r = run(part, extra=("--ckpt-every-dispatch", "1"))
    assert r.returncode == 0, r.stderr[-2000:]
    assert _final_digest(part) == _final_digest(full)


@pytest.mark.slow
def test_train_cli_maps_preemption_to_exit_77(tmp_path):
    """The exit-code contract end-to-end: a SIGTERMed CLI trainer exits
    exactly 77 after checkpointing (the code fleet.py retries)."""
    tmp = str(tmp_path)
    conf_yaml = tmp_path / "conf.yaml"
    conf_yaml.write_text(
        "model:\n  type: wresnet10_1\ndataset: synthetic\naug: default\n"
        "cutout: 0\nbatch: 8\nepoch: 2\nlr: 0.05\n"
        "lr_schedule:\n  type: cosine\n"
        "optimizer:\n  type: sgd\n  decay: 0.0001\n  momentum: 0.9\n"
        "  nesterov: true\n")
    env = dict(os.environ)
    env["FAA_FAULT"] = "sigterm@step=2"
    r = subprocess.run(
        [sys.executable, "-m", "fast_autoaugment_tpu.launch.train_cli",
         "-c", str(conf_yaml), "--dataroot", tmp, "--save",
         f"{tmp}/ck.msgpack", "--cv-ratio", "0.4",
         "--evaluation-interval", "1"],
        env=env, capture_output=True, text=True, timeout=900)
    assert r.returncode == 77, (r.returncode, r.stderr[-2000:])
    assert read_metadata(f"{tmp}/ck.msgpack")["preempted"] is True
