"""chip_smoke.py's verdict logic (host only: the parent never imports
jax, and neither do these tests).  A child result that names the CPU,
a NaN loss, a short step count or a cold second process must each fail
the smoke; on a machine without a chip the script itself exits non-zero
and prints no result line."""

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def _good_result(**over):
    """What train_cli prints for the smoke's command on one v5e chip."""
    result = {
        "epoch": 2, "steps": 8, "loss_train": 2.319, "loss_test": 2.311,
        "top1_test": 0.07, "num_test": 256.0,
        "platform": "tpu", "device_kind": "TPU v5 lite", "device_count": 1,
        "compile_cache": {
            "dir": "/root/repo/.jax_cache", "enabled": True,
            "hits": 125, "misses": 0,
            "labels": {
                "train_dispatch": {"sec": 9.0, "hit": 1, "miss": 0},
                "replay_eval": {"sec": 1.3, "hit": 1, "miss": 0}}},
    }
    result.update(over)
    return result


def _train_problems(result):
    return chip_smoke.check_train_result(
        result, require_platform="tpu", batch_per_device=128)


def test_good_result_passes_every_check():
    good = _good_result()
    assert _train_problems(good) == []
    assert chip_smoke.check_warm_cache(
        good, ("train_dispatch", "replay_eval")) == []
    assert chip_smoke.check_repeat(good, dict(good)) == []
    assert chip_smoke.check_eval_result(
        good, good, require_platform="tpu") == []
    assert chip_smoke.check_rolled_result(good, good) == []


@pytest.mark.parametrize("over, needle", [
    ({"platform": "cpu", "device_kind": "cpu"}, "platform 'cpu'"),
    ({"loss_train": float("nan")}, "loss_train is not finite"),
    ({"loss_test": float("inf")}, "loss_test is not finite"),
    ({"steps": 4}, "took 4 steps, expected 8"),
    ({"epoch": 1}, "ended at epoch 1"),
    ({"num_test": 128.0}, "128.0 test examples"),
    ({"device_count": 0}, "no device_count"),
])
def test_bad_train_result_fails(over, needle):
    problems = _train_problems(_good_result(**over))
    assert any(needle in p for p in problems), problems


def test_expected_steps_follow_the_device_count():
    # the mesh takes every device: 4 chips -> global batch 512, 1 step/epoch
    assert chip_smoke.expected_steps(1, 128) == 8
    assert chip_smoke.expected_steps(4, 128) == 2
    assert _train_problems(_good_result(device_count=4, steps=2)) == []


def test_cold_second_process_fails():
    cold = _good_result()
    cold["compile_cache"] = dict(cold["compile_cache"], hits=0, misses=125)
    assert any("hits=0" in p for p in chip_smoke.check_warm_cache(cold, ()))
    one_miss = _good_result()
    one_miss["compile_cache"]["labels"]["replay_eval"] = {
        "sec": 11.0, "hit": 0, "miss": 1}
    problems = chip_smoke.check_warm_cache(one_miss, ("replay_eval",))
    assert any("'replay_eval' was not a cache hit" in p for p in problems)
    off = _good_result(compile_cache={"dir": None, "enabled": False,
                                      "hits": 0, "misses": 0, "labels": {}})
    assert any("not armed" in p for p in chip_smoke.check_warm_cache(off, ()))


def test_second_run_and_restore_must_reproduce_the_first():
    first = _good_result()
    assert chip_smoke.check_repeat(first, _good_result(loss_train=2.32))
    drifted = _good_result(loss_test=2.4)
    assert any("restored loss_test" in p for p in chip_smoke.check_eval_result(
        drifted, first, require_platform="tpu"))
    assert chip_smoke.check_rolled_result(
        _good_result(loss_train=3.0), first)


def test_search_and_serve_checks():
    result = {"platform": "tpu", "tta_executables": 0,
              "tta_executables_expected": 0, "tta_batched_executables": 1,
              "tta_batched_executables_expected": 1, "num_sub_policies": 37}
    trials = {"0": [[{}, 0.2]] * 4, "1": [[{}, 0.1]] * 4}
    kw = dict(require_platform="tpu", num_fold=2, num_search=4)
    assert chip_smoke.check_search_result(result, trials, **kw) == []
    assert chip_smoke.check_search_result(
        dict(result, tta_batched_executables=2), trials, **kw)
    assert chip_smoke.check_search_result(
        result, {"0": [[{}, float("nan")]] * 4, "1": trials["1"]}, **kw)
    assert chip_smoke.check_search_result(result, {"0": trials["0"]}, **kw)

    sent = {n: np.random.default_rng(n).integers(
        0, 256, (n, 32, 32, 3), dtype=np.uint8) for n in (1, 5)}
    changed = {n: (x // 2) for n, x in sent.items()}
    assert chip_smoke.check_served(changed, sent) == []
    assert chip_smoke.check_served(sent, sent)  # no pixel changed
    assert chip_smoke.check_served(
        {n: np.zeros_like(x) for n, x in sent.items()}, sent)  # constant
    assert chip_smoke.check_served({1: changed[1][:, :16], 5: changed[5]},
                                   sent)  # wrong shape


def _run_smoke(cwd, script, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, script, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=120)


def test_no_chip_exits_nonzero_and_prints_no_result():
    """Where JAX finds no accelerator the smoke fails fast, says why,
    and prints no result line — JAX's own CPU fallback cannot pass it."""
    r = _run_smoke(REPO, os.path.join(REPO, "chip_smoke.py"))
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "no accelerator" in r.stderr


def test_alone_in_a_directory_fails(tmp_path):
    """chip_smoke.py without the repo around it is not a pass either."""
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    r = _run_smoke(str(tmp_path), "chip_smoke.py", "--rehearse")
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "not a checkout" in r.stderr


def _main_stdout(monkeypatch, capsys, tmp_path, device, *argv):
    monkeypatch.setattr(chip_smoke, "OUT_DIR", str(tmp_path / "out"))
    monkeypatch.setattr(chip_smoke, "run", lambda rehearse: {
        "device": dict(device), "steps": 8, "loss_train": 2.3})
    assert chip_smoke.main(list(argv)) == 0
    return [json.loads(ln) for ln
            in capsys.readouterr().out.strip().splitlines()]


def test_rehearsal_can_never_print_the_passing_line(
        monkeypatch, capsys, tmp_path):
    device = {"platform": "cpu", "kind": "cpu", "count": 1}
    summary, last = _main_stdout(
        monkeypatch, capsys, tmp_path, device, "--rehearse")
    assert last == {"ok": False, "device": device}
    assert summary["summary"]["rehearsal"] == "passed"


def test_last_line_is_exactly_the_contracts_object(
        monkeypatch, capsys, tmp_path):
    """The chip check refuses any last stdout line that is not exactly
    ``{"ok", "device": {"platform", "kind", "count"}}`` (PR 21 was
    refused once for carrying the summary there): details go on the
    line before it and into ``summary.json``."""
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    summary, last = _main_stdout(monkeypatch, capsys, tmp_path, device)
    assert last == {"ok": True, "device": device}
    assert list(last) == ["ok", "device"]
    assert list(last["device"]) == ["platform", "kind", "count"]
    assert isinstance(last["device"]["count"], int)
    assert summary["summary"]["steps"] == 8
    with open(tmp_path / "out" / "summary.json") as fh:
        assert json.load(fh) == summary["summary"]
