"""Both programs rehearsed on the CPU at a tiny size (WRN-10-1 on a
400-image fixture): the windows open and close where they should and the
counts agree.  Counts, never times — a CPU run measures nothing."""

import json
import os

import jax
import pytest

from benchmarks import run as runner
from benchmarks.harness import device, spec

LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device", "compared"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}


@pytest.fixture()
def cpu_peaks(monkeypatch):
    """Readers ask for the chip's peaks; the table rightly has none for a
    CPU, so the rehearsal brings its own."""
    monkeypatch.setattr(device, "load_json", lambda path: {
        "cpu": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}})


def _run(root, name, *, trace, seconds, devices):
    cell = spec.resolve_cell(name, seed=4, seconds=seconds, trace=trace,
                             root=root)
    obs = runner.run_cell(cell, devices, runner.process_start_wall())
    return obs, runner.result_line(obs)


@pytest.mark.parametrize("chips", [1, 4])
def test_train_window(make_tiny_checkout, chips):
    """One chip, and the data-parallel mesh of four that the four-chip
    cell kept for later (PERF.md, Open questions) will take."""
    root = make_tiny_checkout(chips_train=chips)
    obs, line = _run(root, "tiny_train", trace=False, seconds=1.0,
                     devices=jax.devices()[:chips])
    assert obs.correct, obs.checks
    assert set(line) == LINE_KEYS and set(line["device"]) == DEVICE_KEYS
    assert set(line["metrics"]) == {"train_images_per_s", "setup_s"}
    assert line["metrics"]["train_images_per_s"]["unit"] == "images/s/chip"
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"  # and so: not a result
    # the window opened after the first epoch boundary plus two dispatches
    steps_per_epoch = 400 // (8 * chips)
    counted = obs.checks["step_counter"]
    assert counted["checkpoint_step"] == counted["steps_counted"]
    assert counted["steps_counted"] == steps_per_epoch + 2 + obs.attempted
    assert obs.checks["no_compile_in_window"]["compile_requests"] == 0
    assert obs.checks["learned"]["num_test"] == 100
    # the stop fell inside an epoch: the snapshot's partial sums
    assert 0.0 <= obs.checks["learned"]["top1_train"] <= 1.0
    assert obs.checks["learned"]["restored_steps"] == counted["steps_counted"]
    assert obs.checks["reference_logits"]["images"] == 16
    # the tiny configuration states both limits, as the file it copies
    assert obs.checks["reference_logits_float32"]["tolerance"] == 1e-5
    # every number compared, beside its limit, last in the line
    assert list(line)[-1] == "compared" and set(line["compared"]) == {
        "no_compile_in_window", "step_counter", "learned", "reference_logits",
        "reference_logits_float32"}
    assert line["compared"]["step_counter"] == {
        "value": counted["steps_counted"], "must": "==",
        "limit": counted["steps_counted"]}
    # rate = steps x global batch / window / chips
    assert line["metrics"]["train_images_per_s"]["value"] == pytest.approx(
        obs.attempted * 8 / obs.window_s)


@pytest.mark.parametrize("meta, expected", [
    ({"step": 40, "in_epoch": {"epoch": 2, "pos": 16, "sums": {
        "loss": 70000.0, "top1": 8192.0, "num": 32768.0}}}, 0.25),
    ({"step": 48, "preempted": True, "metrics": {"top1_train": 0.31}}, 0.31),
    ({"step": 48}, None)],
    ids=["mid_epoch_partial_sums", "epoch_boundary", "neither"])
def test_training_top1_from_either_kind_of_preemption_checkpoint(meta, expected):
    train = spec.load_module("programs", "train")
    assert train.training_top1(meta) == expected


def test_traced_train_run_reports_per_layer_metrics_only(make_tiny_checkout,
                                                         cpu_peaks):
    root = make_tiny_checkout()
    obs, line = _run(root, "tiny_train", trace=True, seconds=30.0,
                     devices=jax.devices()[:1])
    assert obs.correct, obs.checks
    assert obs.window_s < 10  # the traced stretch, not --seconds
    assert set(line) <= LINE_KEYS | {"breakdown"}
    per_layer = {m["name"] for m in obs.cell.per_layer}
    assert set(line["metrics"]) <= per_layer
    assert {"compile_first_call_s", "compile_cache_misses",
            "model_flops_utilization"} <= set(line["metrics"])
    # the CPU backend has no device plane: trace readers find nothing and
    # are left out, and no busy time is claimed
    assert "device_idle_share" not in line["metrics"]
    assert "busy_s" not in line["device"] and "breakdown" not in line
    assert os.path.isdir(os.path.join(obs.cell.work, "trace"))
    assert {name for name, _, _ in obs.host_spans} >= {"dispatch loop", "benchmark"}


#: shorter than any round of the tiny search: the window is then the one
#: round it cuts into, whatever the machine's speed (an 8 s window held
#: no round at all under six test workers, and the watcher gave up)
ONE_ROUND_S = 0.05


def test_search_window(make_tiny_checkout):
    """`search_policies` builds its mesh over every device, so the cell is
    defined on as many chips as this process has."""
    n = len(jax.devices())
    root = make_tiny_checkout(chips_search=n)
    obs, line = _run(root, "tiny_search", trace=False, seconds=ONE_ROUND_S,
                     devices=jax.devices())
    assert obs.correct, obs.checks
    assert set(line) == LINE_KEYS
    assert set(line["metrics"]) == {"search_trials_per_s", "setup_s"}
    # whole rounds of trial_batch=2 after the two warm-up trials, from the
    # last warm-up event to the last event of the round the window cut into
    assert line["attempted"] >= 2 and line["attempted"] % 2 == 0
    assert obs.window_s > ONE_ROUND_S
    trials = [e for e in obs.journal if e["type"] == "trial"]
    assert [t["trial"] for t in trials] == list(range(len(trials)))
    assert line["attempted"] <= len(trials) - 2
    assert obs.checks["first_trial_reproduced"]["logged"] == trials[0]["reward"]
    assert obs.checks["one_tta_executable"]["executables"] == 1
    assert line["metrics"]["search_trials_per_s"]["value"] == pytest.approx(
        line["attempted"] / obs.window_s)
    # the fold checkpoint outlives the run, the trial log does not
    # the cell's one reader of its own, on the journal's spans
    gap = obs.cell.module("layer_metrics", "trial_host_gap_ms").read(obs)
    assert gap is not None and gap >= 0
    kept = os.listdir(os.path.join(obs.cell.work, "fold_checkpoint"))
    assert any(name.endswith(".msgpack") for name in kept)
    assert not any("trials" in name for name in kept)

    # a second run trains nothing: it finds the checkpoint
    obs2, line2 = _run(root, "tiny_search", trace=False, seconds=ONE_ROUND_S,
                       devices=jax.devices())
    assert obs2.correct, obs2.checks
    def first_calls(run):
        return {e["label"] for e in run.journal if e["type"] == "compile"}

    assert "train_dispatch" in first_calls(obs)
    assert "train_dispatch" not in first_calls(obs2)
    assert "tta_batched" in first_calls(obs2)


def test_window_is_taken_from_trial_events():
    search = spec.load_module("programs", "search")
    trials = [{"t_mono": t, "trial": i} for i, t in enumerate(
        [10.0, 10.1, 14.0, 14.1, 18.0, 18.1, 22.0])]
    start, end, inside = search.take_window(trials, 2, 8.0)
    assert (start, end, len(inside)) == (10.1, 18.1, 4)
    # a round longer than the window: the window runs to that round's end
    start, end, inside = search.take_window(trials, 2, 3.0)
    assert (start, end, [t["trial"] for t in inside]) == (10.1, 14.1, [2, 3])
    assert search.take_window(trials[:2], 2, 8.0) is None  # only warm-up
    # a traced window must hold a whole dispatch after the one it cut into
    start, end, inside = search.take_window(trials, 2, 3.0, min_trials=4)
    assert (end, [t["trial"] for t in inside]) == (18.1, [2, 3, 4, 5])
    assert search.take_window(trials, 2, 3.0, min_trials=6) is None
    spans = search._host_spans([
        {"type": "trial", "t_mono": 1.0},
        {"type": "trial", "t_mono": 1.1},
        {"type": "dispatch", "label": "other", "t_mono_start": 1.2, "t_mono_end": 1.3},
        {"type": "dispatch", "label": "tta", "t_mono_start": 1.5, "t_mono_end": 1.9},
        {"type": "dispatch", "label": "tta", "t_mono_start": 2.0, "t_mono_end": 2.9},
        {"type": "trial", "t_mono": 3.0}], ["tta"])
    assert spans == [("between trials", 1.1, 1.5),
                     ("dispatch: enqueue and read-back", 1.5, 1.9),
                     ("dispatch: enqueue and read-back", 2.0, 2.9)]


def test_main_prints_no_result_without_a_tpu(capsys):
    code = runner.main(["--workload", "wrn40x2_train", "--seed", "1",
                        "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert code == 3 and out.out == "" and "no result" in out.err


def test_last_line_has_the_contracts_keys_and_what_was_compared(make_tiny_checkout):
    from benchmarks.harness.observed import Observed

    root = make_tiny_checkout()
    cell = spec.resolve_cell("tiny_train", root=root)
    obs = Observed(cell=cell, devices=jax.devices()[:1], window_s=2.0,
                   end_to_end={"train_images_per_s": 10.0, "setup_s": 3.0,
                               "not_a_metric_of_this_cell": 1.0},
                   attempted=5, failed=0, compile_stats={},
                   checks={"a": {"ok": True}, "b": {
                       "ok": False, "compared": {"value": float("nan"),
                                                 "must": "<=", "limit": 0.02}}},
                   memory_peak_bytes=123)
    line = json.loads(json.dumps(runner.result_line(obs)))
    assert line == {
        "correct": False, "attempted": 5, "failed": 0,
        "metrics": {"train_images_per_s": {"value": 10.0, "unit": "images/s/chip"},
                    "setup_s": {"value": 3.0, "unit": "s"}},
        "device": {"platform": "cpu", "kind": "cpu",
                   "count": len(jax.devices()), "memory_peak_bytes": 123},
        # a reading that is no number goes by its name: the line stays JSON
        "compared": {"b": {"value": "nan", "must": "<=", "limit": 0.02}}}
    obs.checks = {}
    assert not obs.correct  # no check made is not a pass
