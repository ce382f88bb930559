"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py              # on a machine with a TPU
    python chip_smoke.py --rehearse   # CPU rehearsal at a cut size

Drives the trainer's main path once through the entry point a user
calls — ``python -m fast_autoaugment_tpu.launch.train_cli -c
confs/wresnet40x2_cifar.yaml dataset=synthetic epoch=2`` — WRN-40-2
exactly as the conf states it (batch 128 per device, 32 px, the 493
sub-policy ``fa_reduced_cifar10`` tensor, cutout 16, SGD-nesterov,
cosine + warm-up, device cache, one step per dispatch) on the seeded
512/256-example synthetic set: 8 train steps, a replay eval over all
256 test examples and a checkpoint write.  Then the same command again
(a second process must find every executable in the persistent compile
cache and reproduce the first run's losses bit-for-bit), and an
``--only-eval`` restore of the first checkpoint (must reproduce the
first run's test loss).  The remaining device programs follow, one
child each: the rolled multi-step scan (``--steps-per-dispatch 8``),
the phase-2 TTA step through ``search_cli`` (two folds pretrained, four
candidate-vmapped trials each, the audit), and the AOT augment
executables through ``serve_cli`` at its defaults (POSTs of 1, 5 and 33
images through the padded batch shapes, then a SIGTERM drain).

This parent never imports ``jax``: a chip belongs to one process at a
time, so every stage is a child process that owns the chip alone and
has exited before the next starts.  The compile cache lives where
``JAX_COMPILATION_CACHE_DIR`` says, else at the fixed in-checkout path
(``core/compilecache.py``).  Child logs and the search artifacts' JSON
land in ``chiprun_out/chip_smoke/``; checkpoints are deleted at the end
(the chip tool brings back a bounded output directory).

Exit 0 and a last stdout line of exactly ``{"ok": true, "device":
{"platform": "tpu", "kind": "...", "count": N}}`` mean: passed ON THE
CHIP.  What was observed on the way (steps, losses, cold and warm
seconds-to-first-call, cache hits, stage walls) is the stdout line
before it, ``{"summary": {...}}``, also kept as
``chiprun_out/chip_smoke/summary.json``.  JAX falls back to the CPU by
itself when it finds no accelerator — this script does not: a child
that reports any platform but ``tpu`` fails the run, non-zero, with no
result line.  ``--rehearse`` pins the children to the CPU at a cut size
to rehearse the plumbing; it says so and never prints ``"ok": true``.
"""

from __future__ import annotations

import argparse
import http.client
import io
import json
import math
import os
import shutil
import signal
import subprocess
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
CONF = os.path.join("confs", "wresnet40x2_cifar.yaml")
OUT_DIR = os.path.join(ROOT, "chiprun_out", "chip_smoke")

#: the synthetic set (data/datasets.py) and the conf's per-device batch
TRAIN_EXAMPLES, TEST_EXAMPLES, BATCH_PER_DEVICE, EPOCHS = 512, 256, 128, 2
#: the contract allows 1200 s for the whole script, compilation included
TOTAL_BUDGET_S = 1100.0

_PROBE = (
    "import json, jax; d = jax.devices(); "
    "print(json.dumps({'platform': d[0].platform, "
    "'device_kind': d[0].device_kind, 'device_count': len(d)}))")


class SmokeFailure(Exception):
    """A stage failed; the message says which check and why."""


def expected_steps(device_count: int, batch_per_device: int) -> int:
    """Optimizer steps the trainer takes: the mesh takes every device,
    so the global batch is ``batch_per_device x device_count``."""
    return EPOCHS * (TRAIN_EXAMPLES // (batch_per_device * device_count))


def check_device(stamp: dict, require_platform: str) -> list[str]:
    platform = stamp.get("platform")
    if platform != require_platform:
        return [f"JAX reports platform {platform!r}, not "
                f"{require_platform!r} — no accelerator was found (JAX "
                "falls back to the CPU by itself; this smoke does not)"]
    return []


def check_train_result(result: dict, *, require_platform: str,
                       batch_per_device: int) -> list[str]:
    """Every reason a trainer child's result does not count as a pass."""
    problems = check_device(result, require_platform)
    count = int(result.get("device_count") or 0)
    if count < 1:
        return problems + ["result names no device_count"]
    want = expected_steps(count, batch_per_device)
    if result.get("steps") != want:
        problems.append(f"took {result.get('steps')} steps, expected {want}")
    if result.get("epoch") != EPOCHS:
        problems.append(f"ended at epoch {result.get('epoch')}, "
                        f"expected {EPOCHS}")
    for key in ("loss_train", "loss_test"):
        value = result.get(key)
        if not isinstance(value, (int, float)) or not math.isfinite(value):
            problems.append(f"{key} is not finite: {value!r}")
    if result.get("num_test") != TEST_EXAMPLES:
        problems.append(f"evaluated {result.get('num_test')} test examples, "
                        f"expected {TEST_EXAMPLES}")
    return problems


def check_eval_result(result: dict, trained: dict, *,
                      require_platform: str) -> list[str]:
    """The ``--only-eval`` restore against the run that wrote the
    checkpoint: same weights, same 256 examples, same program."""
    problems = check_device(result, require_platform)
    if result.get("steps") != trained.get("steps"):
        problems.append(f"restored step counter {result.get('steps')} != "
                        f"trained {trained.get('steps')}")
    if result.get("num_test") != TEST_EXAMPLES:
        problems.append(f"evaluated {result.get('num_test')} test examples, "
                        f"expected {TEST_EXAMPLES}")
    a, b = result.get("loss_test"), trained.get("loss_test")
    if not (isinstance(a, float) and isinstance(b, float)
            and math.isfinite(a) and math.isclose(a, b, rel_tol=1e-6)):
        problems.append(f"restored loss_test {a!r} != trained {b!r}")
    return problems


def check_warm_cache(result: dict, labels: tuple[str, ...]) -> list[str]:
    """A process that reruns cached programs compiles nothing: process-
    wide ``hits > 0, misses == 0`` and verdict ``hit`` on every seam
    label it executes."""
    cc = result.get("compile_cache") or {}
    problems = []
    if not cc.get("enabled") or not cc.get("dir"):
        problems.append(f"compile cache not armed: {cc!r}")
    if not (cc.get("hits", 0) > 0 and cc.get("misses", 1) == 0):
        problems.append(f"warm process saw hits={cc.get('hits')} "
                        f"misses={cc.get('misses')} (want >0 / 0)")
    for label in labels:
        rec = (cc.get("labels") or {}).get(label) or {}
        if not (rec.get("hit", 0) >= 1 and rec.get("miss", 0) == 0):
            problems.append(f"seam label {label!r} was not a cache hit: "
                            f"{rec!r}")
    return problems


def check_repeat(first: dict, second: dict) -> list[str]:
    """Same seed, same program (one compiled, one deserialized): the
    losses must agree exactly."""
    return [f"{key}: first run {first.get(key)!r} != second "
            f"{second.get(key)!r}"
            for key in ("loss_train", "loss_test", "top1_test")
            if first.get(key) != second.get(key)]


def check_rolled_result(result: dict, first: dict) -> list[str]:
    """The rolled ``lax.scan`` dispatch against the per-step run: same
    seed and data, equal up to the repo's documented multi-step bound
    (tests/test_device_cache.py pins metrics at rtol 5e-2)."""
    a, b = result.get("loss_train"), first.get("loss_train")
    if not (isinstance(a, float) and isinstance(b, float)
            and math.isclose(a, b, rel_tol=5e-2)):
        return [f"rolled-scan loss_train {a!r} vs per-step {b!r}"]
    return []


def check_search_result(result: dict, trials: dict, *, require_platform: str,
                        num_fold: int, num_search: int) -> list[str]:
    """``search_result.json`` + ``search_trials.json`` of the search
    child: every trial evaluated with a finite reward, by exactly the
    executables the policy-as-tensor contract allows."""
    problems = check_device(result, require_platform)
    if "failure" in result:
        problems.append(f"search recorded a failure: {result['failure']!r}")
    for key in ("tta_executables", "tta_batched_executables"):
        if result.get(key) != result.get(key + "_expected"):
            problems.append(f"{key}={result.get(key)} but "
                            f"{result.get(key + '_expected')} expected")
    if sorted(trials) != [str(f) for f in range(num_fold)]:
        problems.append(f"trial log covers folds {sorted(trials)}")
    for fold, log in trials.items():
        rewards = [t[1] if isinstance(t, list) else t.get("reward")
                   for t in log]
        if len(rewards) != num_search or not all(
                isinstance(r, float) and math.isfinite(r) for r in rewards):
            problems.append(f"fold {fold}: {len(rewards)} trials of "
                            f"{num_search}, rewards {rewards!r}")
    if not result.get("num_sub_policies", 0) > 0:
        problems.append("search selected no sub-policy")
    return problems


def check_served(outs: dict, sent: dict) -> list[str]:
    """``outs``/``sent``: request size -> uint8 images.  The default
    dispatch for a many-sub-policy archive is ``grouped`` (one PRNG key
    per dispatch, so no request is reproducible from outside); what can
    be checked is what every augmentation op guarantees on noise input:
    the shape and dtype come back, the policy changed pixels, and no
    image collapsed to a constant."""
    problems = []
    for n, out in outs.items():
        if out.shape != sent[n].shape or out.dtype != np.uint8:
            problems.append(f"POST of {n}: got {out.dtype}{out.shape}, "
                            f"expected uint8{sent[n].shape}")
        elif any(len(np.unique(img)) < 2 for img in out):
            problems.append(f"POST of {n}: an image came back constant")
    biggest = max(outs)
    if not problems and not (outs[biggest] != sent[biggest]).any():
        problems.append("the policy changed no pixel of any image")
    return problems


def _kill_group(proc: subprocess.Popen) -> None:
    try:
        os.killpg(proc.pid, signal.SIGKILL)
    except ProcessLookupError:
        pass
    proc.wait()


def _remaining(name: str, deadline: float) -> float:
    left = deadline - time.monotonic()
    if left <= 0:
        raise SmokeFailure(f"{name}: the {TOTAL_BUDGET_S:.0f}s budget was "
                           "spent before the stage started")
    return left


def _log_tail(name: str) -> str:
    with open(os.path.join(OUT_DIR, f"{name}.log")) as log:
        return log.read()[-3000:]


def run_child(name: str, argv: list[str], env: dict, deadline: float) -> str:
    """Run one stage to its end in its own process group and return its
    stdout.  Its stderr goes to ``OUT_DIR/<name>.log``.  Whatever
    happens, no process survives."""
    timeout = _remaining(name, deadline)
    t0 = time.monotonic()
    with open(os.path.join(OUT_DIR, f"{name}.log"), "w") as log:
        proc = subprocess.Popen(argv, cwd=ROOT, env=env, stdout=subprocess.PIPE,
                                stderr=log, text=True, start_new_session=True)
        try:
            out, _ = proc.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            raise SmokeFailure(f"{name}: killed after {timeout:.0f}s (budget "
                               f"spent)\n{_log_tail(name)}") from None
        finally:
            _kill_group(proc)
    wall = time.monotonic() - t0
    if proc.returncode != 0:
        raise SmokeFailure(f"{name}: exit code {proc.returncode} after "
                           f"{wall:.0f}s\n{_log_tail(name)}")
    print(f"[chip_smoke] {name}: ran {wall:.0f}s", file=sys.stderr, flush=True)
    return out


def last_json(name: str, stdout: str) -> dict:
    lines = [ln for ln in stdout.splitlines() if ln.strip()]
    try:
        return json.loads(lines[-1])
    except (IndexError, ValueError):
        raise SmokeFailure(f"{name}: no JSON result on its last stdout "
                           f"line: {stdout[-500:]!r}") from None


def _require(name: str, problems: list[str]) -> None:
    if problems:
        raise SmokeFailure(f"{name}: " + "; ".join(problems))


def _read_json(name: str, path: str) -> dict:
    try:
        with open(path) as fh:
            return json.load(fh)
    except (OSError, ValueError) as e:
        raise SmokeFailure(f"{name}: cannot read {path}: {e}") from None


def _http(port: int, method: str, path: str, body: bytes | None = None,
          timeout: float = 120.0) -> bytes:
    """One request to the serve child; anything but a 200 fails the stage."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request(method, path, body=body)
        resp = conn.getresponse()
        payload = resp.read()
    except (OSError, http.client.HTTPException) as e:
        raise SmokeFailure(f"serve: {method} {path} failed: {e!r}") from None
    finally:
        conn.close()
    if resp.status != 200:
        raise SmokeFailure(f"serve: {method} {path} answered {resp.status}: "
                           f"{payload[:300]!r}")
    return payload


def serve_stage(env: dict, deadline: float, require: str) -> dict:
    """``serve_cli`` at its defaults (32 px, AOT shapes 1,8,32,128): wait
    for the port file, POST 1, 5 and 33 images, read ``/stats``, SIGTERM,
    and expect the graceful-drain exit 0."""
    name = "serve"
    _remaining(name, deadline)
    port_file = os.path.join(OUT_DIR, "serve.port")
    if os.path.exists(port_file):
        os.remove(port_file)
    rng = np.random.default_rng(0)
    images = rng.integers(0, 256, (33, 32, 32, 3), dtype=np.uint8)
    t0 = time.monotonic()
    with open(os.path.join(OUT_DIR, f"{name}.log"), "w") as log:
        proc = subprocess.Popen(
            [sys.executable, "-m", "fast_autoaugment_tpu.serve.serve_cli",
             "--policy", "fa_reduced_cifar10", "--port", "0",
             "--port-file", port_file],
            cwd=ROOT, env=env, stdout=log, stderr=log,
            start_new_session=True)
        try:
            while not os.path.exists(port_file):
                if proc.poll() is not None:
                    raise SmokeFailure(f"{name}: server exited "
                                       f"{proc.returncode} before it bound a "
                                       f"port\n{_log_tail(name)}")
                if time.monotonic() > deadline:
                    raise SmokeFailure(f"{name}: no port within the budget"
                                       f"\n{_log_tail(name)}")
                time.sleep(0.5)
            ready_secs = time.monotonic() - t0
            with open(port_file) as fh:
                port = int(fh.read().strip())
            outs, sent = {}, {}
            for n in (1, 5, 33):
                body = io.BytesIO()
                np.savez(body, images=images[:n])
                payload = _http(port, "POST", "/augment", body.getvalue())
                outs[n] = np.load(io.BytesIO(payload))["images"]
                sent[n] = images[:n]
            stats = json.loads(_http(port, "GET", "/stats"))
            proc.send_signal(signal.SIGTERM)
            try:
                code = proc.wait(timeout=60)
            except subprocess.TimeoutExpired:
                raise SmokeFailure(f"{name}: no exit within 60s of SIGTERM"
                                   f"\n{_log_tail(name)}") from None
        finally:
            _kill_group(proc)
    _require(name, check_device(stats, require) + check_served(outs, sent)
             + ([] if code == 0 else [f"drain exit code {code}, expected 0"]))
    print(f"[chip_smoke] {name}: ran {time.monotonic() - t0:.0f}s",
          file=sys.stderr, flush=True)
    return {"ready_secs": round(ready_secs, 1),
            "aot_compile": stats.get("aot_compile"),
            "requests": sorted(outs), "drain_exit": code}


def run(rehearse: bool) -> dict:
    require = "cpu" if rehearse else "tpu"
    env = dict(os.environ)
    cut: list[str] = []
    batch = BATCH_PER_DEVICE
    if rehearse:
        env["JAX_PLATFORMS"] = "cpu"
        batch = 8
        cut = [f"batch={batch}", "model.type=wresnet10_1"]
    for needed in (CONF, os.path.join("fast_autoaugment_tpu", "launch",
                                      "train_cli.py")):
        if not os.path.isfile(os.path.join(ROOT, needed)):
            raise SmokeFailure(f"{needed} is not beside chip_smoke.py — "
                               "this is not a checkout of the repo")
    # start clean: a checkpoint left by an earlier run would be resumed
    # and nothing would train
    shutil.rmtree(OUT_DIR, ignore_errors=True)
    os.makedirs(OUT_DIR)
    deadline = time.monotonic() + TOTAL_BUDGET_S
    py = sys.executable
    walls: dict[str, float] = {}

    def child(name: str, argv: list[str]) -> str:
        t0 = time.monotonic()
        out = run_child(name, argv, env, deadline)
        walls[name] = round(time.monotonic() - t0, 1)
        return out

    device = last_json("devices", child("devices", [py, "-c", _PROBE]))
    _require("devices", check_device(device, require))

    def train_cli(name: str, save: str, *extra: str) -> dict:
        return last_json(name, child(
            name, [py, "-m", "fast_autoaugment_tpu.launch.train_cli",
                   "-c", CONF, "--save", os.path.join(OUT_DIR, save), *extra,
                   "dataset=synthetic", f"epoch={EPOCHS}", *cut]))

    first = train_cli("train_first", "first.msgpack")
    _require("train_first", check_train_result(
        first, require_platform=require, batch_per_device=batch))
    if not os.path.isfile(os.path.join(OUT_DIR, "first.msgpack")):
        raise SmokeFailure("train_first: wrote no checkpoint")

    second = train_cli("train_second", "second.msgpack")
    _require("train_second", check_train_result(
        second, require_platform=require, batch_per_device=batch)
        + check_warm_cache(second, ("train_dispatch", "replay_eval"))
        + check_repeat(first, second))

    restored = train_cli("only_eval", "first.msgpack", "--only-eval")
    _require("only_eval", check_eval_result(
        restored, first, require_platform=require)
        + check_warm_cache(restored, ("replay_eval",)))

    rolled = train_cli("train_rolled", "rolled.msgpack",
                       "--steps-per-dispatch", "8")
    _require("train_rolled", check_train_result(
        rolled, require_platform=require, batch_per_device=batch)
        + check_rolled_result(rolled, first))

    num_fold, num_search = 2, 4
    search_dir = os.path.join(OUT_DIR, "search")
    child("search", [py, "-m", "fast_autoaugment_tpu.launch.search_cli",
                     "-c", CONF, "--save-dir", search_dir,
                     "--num-fold", str(num_fold),
                     "--num-search", str(num_search), "--trial-batch", "2",
                     "--until", "2", "--fold-quality-floor", "off",
                     "dataset=synthetic", "epoch=1", *cut])
    searched = _read_json(
        "search", os.path.join(search_dir, "search_result.json"))
    trials = _read_json(
        "search", os.path.join(search_dir, "search_trials.json"))
    _require("search", check_search_result(
        searched, trials, require_platform=require, num_fold=num_fold,
        num_search=num_search))

    t_serve = time.monotonic()
    served = serve_stage(env, deadline, require)
    walls["serve"] = round(time.monotonic() - t_serve, 1)

    def cache_use(result: dict) -> dict:
        cc = result["compile_cache"]
        return {"first_call_secs": {label: rec["sec"] for label, rec
                                    in cc["labels"].items()},
                "cache_hits": cc["hits"], "cache_misses": cc["misses"]}

    return {
        "device": {"platform": str(device["platform"]),
                   "kind": str(device["device_kind"]),
                   "count": int(device["device_count"])},
        "model": "wresnet10_1 (rehearsal cut)" if rehearse else "wresnet40_2",
        "batch_per_device": batch,
        "steps": first["steps"],
        "loss_train": first["loss_train"],
        "loss_test": first["loss_test"],
        "num_test": first["num_test"],
        "restored_loss_test": restored["loss_test"],
        "rolled_loss_train": rolled["loss_train"],
        "compile_cache_dir": second["compile_cache"]["dir"],
        "first_run": cache_use(first),
        "second_run": cache_use(second),
        "rolled_run": cache_use(rolled),
        "search": {"num_sub_policies": searched["num_sub_policies"],
                   "tta_batched_executables":
                       searched["tta_batched_executables"],
                   **cache_use(searched)},
        "serve": served,
        "stage_wall_secs": walls,
    }


def verdict_line(ok: bool, device: dict) -> str:
    """The last stdout line, to the letter of the chip check's contract:
    exactly the keys ``ok`` and ``device``, the device exactly
    ``platform``, ``kind`` and ``count`` as JAX reported them.  Anything
    else the run learned belongs on the summary line before it."""
    return json.dumps({"ok": bool(ok), "device": {
        "platform": device["platform"], "kind": device["kind"],
        "count": device["count"]}})


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--rehearse", action="store_true",
                        help="CPU rehearsal at a cut size (wresnet10_1, "
                             "batch 8); never prints the passing line")
    args = parser.parse_args(argv)
    t0 = time.monotonic()
    try:
        summary = run(args.rehearse)
    except SmokeFailure as e:
        print(f"[chip_smoke] FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    finally:
        for dirpath, _dirs, files in os.walk(OUT_DIR):
            for name in files:
                if ".msgpack" in name and not name.endswith(".jsonl"):
                    os.remove(os.path.join(dirpath, name))
    summary["total_secs"] = round(time.monotonic() - t0, 1)
    if args.rehearse:
        print("[chip_smoke] REHEARSAL on the CPU at a cut size — this is "
              "NOT a chip result", file=sys.stderr, flush=True)
        summary["rehearsal"] = "passed"
    os.makedirs(OUT_DIR, exist_ok=True)
    with open(os.path.join(OUT_DIR, "summary.json"), "w") as fh:
        json.dump(summary, fh, indent=1)
    print(json.dumps({"summary": summary}))
    print(verdict_line(not args.rehearse, summary["device"]), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
