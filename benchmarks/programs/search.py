"""Phase 2: ``search_policies`` on one fold, a window taken between two
``trial`` events of the program's telemetry journal.

The search loop is the program's.  Its phase-2 loop polls no stop flag,
so the window is closed from outside, the way a user stops a search: a
watcher thread signals the main thread, whose handler raises
:class:`WindowClosed` out of ``search_policies``.  Trials are counted
from the journal (``--telemetry DIR``, a documented user flag; a fixed
few tens of microseconds per dispatch), whose ``trial`` events are
stamped once the reward is on the host: the window runs from the last
warm-up trial's event to the last event inside ``--seconds``, so it holds
whole trials the device has finished, and the rate is their number over
exactly their span.

Set-up: the fixture; phase-1 pretraining of the fold, which the first
run in a checkout pays and later runs skip because the fold's checkpoint
is kept under ``bench_work/`` (the trial log is not kept); the warm-up
trials, the first of which compiles or loads the TTA program.

No cell of ``BENCHMARK.json`` names this program yet: at the sizes the
memory floor forces, a window held one round of three start-up trials
(PERF.md, Open questions, says what a cell needs first).  It ran on the
chip, ``correct``, and the self-tests rehearse it.  A traffic file for
it gives: ``entry_args`` for ``search_policies`` (``folds`` with one
fold, ``num_policy``, ``num_op``, ``cv_ratio`` and what else the cell
fixes), ``conf_overrides``, ``warmup_trials`` before the window,
``close_margin_seconds`` and ``max_window_factor`` for rounds that
outlast it, ``trace_seconds`` and ``trace_min_trials``, the journal's
``dispatch_labels``, the ``step_program`` pattern, ``reward_tolerance``
and ``reference_images``.
"""

from __future__ import annotations

import glob
import itertools
import json
import math
import os
import shutil
import signal
import threading
import time

from benchmarks.harness import window as win
from benchmarks.harness.device import device_barrier, memory_peak_bytes
from benchmarks.harness.fixture import write_fixture
from benchmarks.harness.observed import Observed
from benchmarks.harness.spec import Cell

_STOP_SIGNAL = signal.SIGUSR2  # the program handles SIGTERM and SIGUSR1
#: the program keeps its journal open for the life of the process, so a
#: second run in one process (the self-tests make one) gets a new place
_RUN_NUMBER = itertools.count()


class WindowClosed(BaseException):
    """Raised in the main thread when the window is over (a
    BaseException: the search quarantines ordinary errors of a trial)."""


def read_journal(directory: str) -> list[dict]:
    """Every event of the journal's segments, in the order written."""
    events = []
    for path in sorted(glob.glob(os.path.join(directory, "journal-*.jsonl"))):
        with open(path) as fh:
            for line in fh:
                try:
                    events.append(json.loads(line))
                except ValueError:
                    pass  # the tail a stopped writer left half-written
    return sorted(events, key=lambda e: e.get("seq", 0))


class _Watcher(threading.Thread):
    """Follows the journal from a second thread: when the warm-up trials
    are in, snapshots the counters (and starts the profiler), and when
    the window's length has passed since, stops the search."""

    def __init__(self, cell: Cell, journal_dir: str, main_thread_id: int):
        super().__init__(name="bench-watcher", daemon=True)
        self.cell = cell
        self.journal_dir, self.main_thread_id = journal_dir, main_thread_id
        traffic = cell.traffic
        self.warmup_trials = int(traffic["warmup_trials"])
        self.seconds, self.tracer = win.window_plan(cell)
        # a trace that starts mid-dispatch needs a whole dispatch after it
        self.min_trials = int(traffic["trace_min_trials"]) if cell.trace else 1
        self.done = threading.Event()
        self.opened_perf: float | None = None
        self.compile_stats: dict = {}
        self.compiles0: int | None = None
        self.error: BaseException | None = None

    def _trials_logged(self) -> int:
        from fast_autoaugment_tpu.core import telemetry

        telemetry.journal_flush()
        count = 0
        for path in glob.glob(os.path.join(self.journal_dir, "journal-*.jsonl")):
            with open(path) as fh:
                count += sum('"type":"trial"' in line for line in fh)
        return count

    def run(self) -> None:
        from fast_autoaugment_tpu.core.compilecache import compile_cache_stats

        try:
            while self._trials_logged() < self.warmup_trials:
                if self.done.wait(0.05):
                    return
            self.compile_stats = compile_cache_stats()
            self.compiles0 = win.compile_requests(self.compile_stats)
            if self.tracer is not None:
                self.tracer.start()
            self.opened_perf = time.perf_counter()
            # the window opened at the last warm-up trial's event, which
            # is before this thread saw it: waiting its whole length from
            # here always covers it
            margin = float(self.cell.traffic["close_margin_seconds"])
            if self.done.wait(self.seconds + margin):
                return
            # rounds longer than the window: wait for as many as the
            # window must hold at least to end, but not for ever
            give_up = self.opened_perf + self.seconds * float(
                self.cell.traffic["max_window_factor"])
            while (self._trials_logged() < self.warmup_trials + self.min_trials
                   and time.perf_counter() < give_up):
                if self.done.wait(0.05):
                    return
            if self.done.wait(margin):  # the round's last events
                return
            signal.pthread_kill(self.main_thread_id, _STOP_SIGNAL)
        except BaseException as e:  # surfaced by the main thread
            self.error = e
            signal.pthread_kill(self.main_thread_id, _STOP_SIGNAL)


def _raise_window_closed(signum, frame):
    raise WindowClosed()


def take_window(trials: list[dict], warmup_trials: int, seconds: float,
                min_trials: int = 1, round_span: float = 0.25):
    """``(start_mono, end_mono, events_in_window)`` from the journal's
    trial events (in order): the window starts at warm-up trial number
    `warmup_trials` and ends at the last trial within `seconds` of it.
    Where fewer than `min_trials` ended that soon, the window runs to the
    end of the round that brings them: the `min_trials`-th trial's event
    and those stamped within `round_span` seconds of it (a round's events
    are written in one loop)."""
    later = trials[warmup_trials:]
    if len(trials) <= warmup_trials or len(later) < min_trials:
        return None
    start = float(trials[warmup_trials - 1]["t_mono"])
    reach = max(start + seconds,
                float(later[min_trials - 1]["t_mono"]) + round_span)
    inside = [t for t in later if float(t["t_mono"]) <= reach]
    return start, float(inside[-1]["t_mono"]), inside


def run(cell: Cell, devices: list, start_wall: float) -> Observed:
    import jax
    import jax.numpy as jnp

    from fast_autoaugment_tpu.core import telemetry
    from fast_autoaugment_tpu.core.compilecache import configure_compile_cache
    from fast_autoaugment_tpu.core.config import Config
    from fast_autoaugment_tpu.data.datasets import load_dataset
    from fast_autoaugment_tpu.policies.archive import (
        policy_decoder,
        policy_to_tensor,
    )
    from fast_autoaugment_tpu.parallel.mesh import make_mesh
    from fast_autoaugment_tpu.search.census import executable_census
    from fast_autoaugment_tpu.search.driver import _FoldEval, search_policies

    traffic = cell.traffic
    if len(jax.devices()) != cell.chips:
        raise RuntimeError(
            f"search_policies builds its mesh over all {len(jax.devices())} "
            f"devices; this cell is defined on {cell.chips}")
    configure_compile_cache()
    dataroot = write_fixture(os.path.join(cell.work, "data"), cell.fixture,
                             cell.seed)
    conf = Config(cell.conf_dict())
    entry_args = dict(traffic["entry_args"])
    fold = int(entry_args["folds"][0])

    # the fold checkpoint outlives the run; the trial log and the rest do not
    save_dir = os.path.join(cell.work, "search")
    kept_dir = os.path.join(cell.work, "fold_checkpoint")
    journal_dir = os.path.join(cell.work, "telemetry")
    for d in (save_dir, journal_dir):
        shutil.rmtree(d, ignore_errors=True)
    journal_dir = os.path.join(journal_dir, f"run{next(_RUN_NUMBER)}")
    if os.path.isdir(kept_dir):
        shutil.copytree(kept_dir, save_dir)
    else:
        os.makedirs(save_dir)

    watcher = _Watcher(cell, journal_dir, threading.get_ident())
    previous = signal.signal(_STOP_SIGNAL, _raise_window_closed)
    watcher.start()
    finished = False
    try:
        search_policies(conf, dataroot, save_dir, seed=cell.seed,
                        telemetry_spec=journal_dir, **entry_args)
        finished = True  # the trial budget ended before the window did
    except WindowClosed:
        pass
    finally:
        signal.signal(_STOP_SIGNAL, signal.SIG_IGN)
        watcher.done.set()
        watcher.join(timeout=30)
        signal.signal(_STOP_SIGNAL, previous)
    if watcher.error is not None:
        raise watcher.error
    device_barrier()
    compiles1 = win.compile_requests()
    memory_peak = memory_peak_bytes(devices)
    if watcher.tracer is not None and watcher.tracer.running:
        watcher.tracer.stop()
    telemetry.journal_flush()
    journal = read_journal(journal_dir)

    if not os.path.isdir(kept_dir):
        os.makedirs(kept_dir)
        for path in glob.glob(os.path.join(save_dir, "*fold*.msgpack*")):
            shutil.copy2(path, kept_dir)

    trials = [e for e in journal if e["type"] == "trial"]
    warmup = int(traffic["warmup_trials"])
    taken = take_window(trials, warmup, watcher.seconds, watcher.min_trials)
    checks: dict[str, dict] = {}
    if taken is None:
        checks["window"] = {"ok": False, "trials_logged": len(trials),
                            "why": "no whole trial after the warm-up"}
        return Observed(cell=cell, devices=devices, end_to_end={},
                        window_s=0.0, attempted=0, failed=0, checks=checks,
                        compile_stats=watcher.compile_stats,
                        memory_peak_bytes=memory_peak, journal=journal)
    start, end, inside = taken
    window_s = end - start
    rate = len(inside) / window_s
    # perf_counter is the journal's t_mono clock
    setup_s = (time.time() - start_wall) - (time.perf_counter() - start)
    failed = sum(bool(t.get("quarantined")) for t in inside)
    rewards = [float(t["reward"]) for t in trials]
    checks["rewards"] = {
        "ok": all(math.isfinite(r) and 0.0 <= r <= 1.0 for r in rewards),
        "trials": len(rewards), "min": min(rewards), "max": max(rewards),
        "budget_ended_in_window": finished}
    seam_compiles = [e for e in journal if e["type"] == "compile"
                     and start < float(e["t_mono"]) <= end]
    checks["no_compile_in_window"] = {
        "ok": not seam_compiles and compiles1 == watcher.compiles0,
        "seam_first_calls": [e["label"] for e in seam_compiles],
        "compile_requests_since_open": compiles1 - watcher.compiles0}

    # -- outside the window ---------------------------------------------
    with open(os.path.join(save_dir, "search_trials.json")) as fh:
        logged = json.load(fh)[str(fold)]
    evaluator = _FoldEval(
        conf, dataroot, make_mesh(), num_policy=int(entry_args["num_policy"]),
        num_op=int(entry_args["num_op"]),
        cv_ratio=float(entry_args["cv_ratio"]), seed=cell.seed)
    fold_path = glob.glob(os.path.join(save_dir, f"*fold{fold}_*.msgpack"))[0]
    params, batch_stats = evaluator.load_fold(fold_path)
    proposal, logged_reward = logged[0][0], float(logged[0][1])
    policy_t = jnp.asarray(policy_to_tensor(policy_decoder(
        proposal, int(entry_args["num_policy"]), int(entry_args["num_op"]))))
    key = jax.random.fold_in(jax.random.PRNGKey(cell.seed * 77 + fold), 0)
    replayed = evaluator.evaluate(fold, params, batch_stats, policy_t, key)
    again = float(replayed["top1_valid"])
    checks["first_trial_reproduced"] = {
        "ok": abs(again - logged_reward) <= float(traffic["reward_tolerance"]),
        "logged": logged_reward, "re_evaluated": again,
        "tolerance": float(traffic["reward_tolerance"]),
        "compared": win.compared(abs(again - logged_reward), "<=",
                                 float(traffic["reward_tolerance"]))}
    census = executable_census(evaluator.tta_step)
    checks["one_tta_executable"] = {"ok": census == 1, "executables": census,
                                    "compared": win.compared(census, "==", 1)}

    images = load_dataset(conf["dataset"], dataroot)[1].images[
        :int(traffic["reference_images"])]
    checks.update(win.reference_check(cell, conf, params, batch_stats, images))

    # a trial is the whole held-out fold under every draw
    forwards = float(replayed["cnt"]) * int(entry_args["num_policy"])
    host_spans = _host_spans(journal, traffic["dispatch_labels"])
    return Observed(
        cell=cell, devices=devices,
        end_to_end={"search_trials_per_s": rate, "setup_s": setup_s},
        window_s=window_s, attempted=len(inside), failed=failed,
        checks=checks, compile_stats=watcher.compile_stats,
        memory_peak_bytes=memory_peak,
        work={"images_per_s_per_chip": rate * forwards / len(devices),
              "passes": "forward"},
        step_program=traffic["step_program"],
        trace_dir=watcher.tracer.directory if watcher.tracer else None,
        host_spans=host_spans,
        marker_perf=watcher.tracer.marker_perf if watcher.tracer else None,
        journal=journal)


def _host_spans(journal: list[dict], dispatch_labels: list[str]):
    """What the host was doing, from the journal's own stamps: ``between
    trials`` from a round's last trial event to the start of the next
    dispatch the journal recorded, and ``dispatch: enqueue and read-back``
    over each dispatch (the device is idle at its edges: before the
    program starts, and from its end until the host has the rewards)."""
    spans, last_trial = [], None
    for ev in journal:
        if ev["type"] == "trial":
            last_trial = float(ev["t_mono"])
        elif ev["type"] == "dispatch" and ev.get("label") in dispatch_labels:
            if last_trial is not None:
                spans.append(("between trials", last_trial,
                              float(ev["t_mono_start"])))
                last_trial = None
            spans.append(("dispatch: enqueue and read-back",
                          float(ev["t_mono_start"]), float(ev["t_mono_end"])))
    return spans
