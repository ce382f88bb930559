"""``train_and_eval`` on a token data set: ids in, next-token loss, no
policy, through the device cache and ``jit_multi_fn``; a window taken
between two of its dispatches.

``programs/train.py``'s heartbeat, markers and window, by import and
unchanged, and its ``no_compile_in_window`` and ``step_counter`` checks;
what differs is what that file cannot do for this path:

- the fixture is ``harness/fixture_tokens.py``'s two ``.npy`` files of ids;
- ``learned`` reads the trainer's own training *loss* at the preemption
  checkpoint against ``ln(ids held)`` less the traffic file's margin (a
  token model has no chance accuracy worth a floor).  There is no
  ``only_eval`` restore here: the checkpoint is 7.2 GB of float32 state
  (weights and AdamW's two moments), half a minute to read whole (my
  chip run, PR 35), and a run has 360 s; the program reads its parameters
  once, for the reference comparison, and the CPU tests hold the
  ``only_eval`` path;
- the reference comparison is of next-token logits: the system's model
  from the checkpoint's weights on ``reference_sequences`` test
  sequences at the timed length, as deployed (``reference_logits``) and
  under ``highest`` (``reference_logits_float32``), against the
  configuration's plain reference (``harness/window.py::logits_agreement``
  decides both, by the configuration's two tolerances).  **The reference
  is given the system's choice of experts** (the model ``sow``s it into
  ``routing``), once for each of the two passes, and two more checks
  (``routing``, ``routing_float32``) hold that choice to the reference's
  own scores: under a balanced router hundreds of tokens have their
  eighth and ninth expert within rounding, and two sound computations
  that each take their own top-k differ by 5-15% of the largest logit
  (``references/kimi_linear.py``; my chip run, PR 35).  Both programs of
  that comparison (the system's two forward passes, the reference's) are
  compiled from shapes on two threads of their own, started when the
  window has closed, while the trainer writes its checkpoint: compiled
  after it they added 85 s to a cold run, and started beside the
  trainer's own set-up compilations they cost those a third of what they
  saved (my chip runs, PR 35).  Of the checkpoint the comparison reads
  the ``params`` entry alone;
- the program's token counters (``faa_tokens_total``,
  ``faa_moe_assignments_total``, ``faa_moe_held_load_max_over_mean``:
  published where the trainer syncs its sums, at an epoch boundary and
  at the preemption snapshot) are read when the window opens and after
  the trainer has stopped, and what they say goes, through
  ``Observed.work``, to the readers ``moe_experts_roofline`` and
  ``moe_held_load_max_over_mean``.  A program without them hands over
  nothing and those readers report nothing.
"""

from __future__ import annotations

import inspect
import math
import os
import shutil
import threading

import numpy as np

from benchmarks.harness import window as win
from benchmarks.harness.device import memory_peak_bytes
from benchmarks.harness.fixture_tokens import write_fixture
from benchmarks.harness.observed import Observed
from benchmarks.harness.spec import Cell
from benchmarks.programs import train

TOKEN_COUNTERS = ("faa_tokens_total", "faa_moe_")
LOAD_GAUGE = "faa_moe_held_load_max_over_mean"


def token_counters() -> dict[str, float]:
    """The program's token counters now, ``{name{labels}: value}``; empty
    where the program has none."""
    from fast_autoaugment_tpu.core import telemetry

    return {key: value
            for key, value in telemetry.registry().counters_snapshot().items()
            if key.startswith(TOKEN_COUNTERS)}


def held_load_gauges() -> dict[str, float]:
    """``{layer: value}`` of the program's gauge
    ``faa_moe_held_load_max_over_mean{layer}``."""
    from fast_autoaugment_tpu.core import telemetry

    return {key.split('layer="')[1].split('"')[0]: value
            for key, value in telemetry.registry().snapshot()["gauges"].items()
            if key.startswith(LOAD_GAUGE + "{")}


class CountersAtTheOpening:
    """``train._Beat`` with the token counters read as the window opens,
    and `ahead` started as it closes."""

    def __init__(self, beat: "train._Beat", ahead: threading.Thread):
        self.beat, self.ahead = beat, ahead
        self.opened: dict[str, float] | None = None

    def __call__(self) -> None:
        before = self.beat.state
        self.beat()
        if before == "warmup" and self.beat.state == "open":
            self.opened = token_counters()
        elif before == "open" and self.beat.state == "closed":
            self.ahead.start()

    def counted_since(self, tokens_a_step: int) -> dict[str, float]:
        """What the counters say of the steps since the window opened
        (to the trainer's stop, a few steps past the window's end): the
        steps they cover and the held experts' assignments a step, in all
        and by layer.  Empty where the program has no such counters."""
        now = token_counters()
        if self.opened is None or not now:
            return {}

        def rise(prefix):
            return {key: value - self.opened.get(key, 0.0)
                    for key, value in now.items() if key.startswith(prefix)}

        steps = sum(rise("faa_tokens_total").values()) / tokens_a_step
        if steps <= 0:
            return {}
        by_layer = {key.split('layer="')[1].split('"')[0]: value / steps
                    for key, value in rise("faa_moe_assignments_total").items()}
        return {"counted_steps": steps,
                "moe_assignments_a_step": sum(by_layer.values()),
                "moe_assignments_a_step_by_layer": by_layer,
                "moe_held_load_max_over_mean": held_load_gauges()}


def training_loss(meta: dict) -> float | None:
    """The trainer's own training loss as its preemption checkpoint
    records it: the running mean of the epoch in progress, or the
    finished epoch's where the stop fell on an epoch boundary."""
    sums = (meta.get("in_epoch") or {}).get("sums")
    if sums:
        return float(sums["loss"]) / float(sums["num"])
    value = (meta.get("metrics") or {}).get("loss_train")
    return None if value is None else float(value)


def learned_check(meta: dict, ids: int, margin: float) -> dict:
    """That the steps counted were training: the training loss at the
    checkpoint at least `margin` under ``ln(ids)``, what no learning
    reads."""
    loss_train = training_loss(meta)
    limit = math.log(ids) - margin
    reading = float("nan") if loss_train is None else loss_train
    return {"ok": reading <= limit, "loss_train": loss_train,
            "loss_of_no_learning": math.log(ids), "loss_must_not_pass": limit,
            "compared": win.compared(reading, "<=", limit)}


def checkpoint_params(path: str) -> dict:
    """The ``params`` entry of a trainer checkpoint (flax's msgpack of the
    ``TrainState``), without restoring the optimizer's two moments after
    it: two thirds of 7.2 GB in this cell."""
    import msgpack
    from flax import serialization

    with open(path, "rb") as fh:
        entries = msgpack.Unpacker(fh, max_buffer_size=0)
        for _ in range(entries.read_map_header()):
            key, start = entries.unpack(), entries.tell()
            entries.skip()
            if key == "params":
                end = entries.tell()
                fh.seek(start)
                return serialization.msgpack_restore(fh.read(end - start))
    raise KeyError(f"{path}: no params entry")


class ComparisonsAhead(threading.Thread):
    """The two programs of the reference comparison, compiled from shapes
    while the trainer writes its checkpoint: the system's model on ids
    ``[n, T]`` as deployed and under ``highest`` (one program for both:
    the precision is read at trace time; each with the experts it chose),
    and the configuration's plain reference given such a choice."""

    def __init__(self, cell: Cell, conf, sequences: int, length: int):
        import jax
        import jax.numpy as jnp

        from fast_autoaugment_tpu.models import get_model, model_conf_of

        super().__init__(name="comparisons-ahead")
        self.sizes = cell.config["model"]
        self.reference = cell.module("references", cell.config["reference"])
        self.model = get_model(model_conf_of(conf), int(self.sizes["ids_held"]))
        self.ids = jax.ShapeDtypeStruct((sequences, length), jnp.int32)
        self.system = self.plain = self.failed = None

    def run(self) -> None:
        import jax

        def system(p, x):
            """((logits, routing) as deployed, the same under highest)"""
            def apply():
                logits, sown = self.model.apply({"params": p}, x, train=False,
                                                mutable=["routing"])
                return logits, {layer: entry["moe"]["chosen"][0] for layer, entry
                                in sown.get("routing", {}).items()}

            deployed = apply()
            with jax.default_matmul_precision("highest"):
                return deployed, apply()

        def keep(compile_one):
            try:
                compile_one()
            except BaseException as e:  # handed to whoever asks for the programs
                self.failed = e

        def compile_system():
            self.system = jax.jit(system).lower(params, self.ids).compile()

        def compile_reference():
            self.plain = self.reference.compile_forward_given_routing(
                params, self.ids, self.sizes)

        try:
            params = jax.eval_shape(
                lambda x: self.model.init(jax.random.PRNGKey(0), x)["params"],
                self.ids)
        except BaseException as e:
            self.failed = e
            return
        # side by side: one after the other they outlast the checkpoint's
        # writing by a minute of a cold run (my chip runs, PR 35)
        beside = threading.Thread(target=keep, args=(compile_reference,),
                                  name="comparisons-ahead-reference")
        beside.start()
        keep(compile_system)
        beside.join()

    def programs(self):
        self.join()
        if self.failed is not None:
            raise self.failed
        return self.system, self.plain


def reference_checks(cell: Cell, ahead: ComparisonsAhead, params,
                     ids: np.ndarray) -> dict[str, dict]:
    """The system's logits for the inputs ``ids[:, :-1]`` against the plain
    reference's: as deployed, and with the system under ``highest``
    (``harness/window.py::reference_check``'s two comparisons, for a model
    that takes ids and has no evaluation preprocessing), the reference
    given the experts the system chose in that pass; and that choice
    against the reference's own scores (``routing``, ``routing_float32``:
    how far a chosen expert lies under the reference's ``top_k``-th, by
    the configuration's two ``routing_margin_tolerance`` s)."""
    system, plain_forward = ahead.programs()
    inputs = np.ascontiguousarray(ids[:, :-1])
    checks = {}
    for suffix, (logits, routing) in zip(("", "_float32"), system(params, inputs)):
        routing = {layer: np.asarray(chosen) for layer, chosen in routing.items()}
        if not routing:
            checks["routing" + suffix] = {"ok": False, "why": "the model sowed no "
                                          "choice of experts to give the reference"}
            continue
        plain, margin = plain_forward(params, inputs, routing)
        checks["reference_logits" + suffix] = win.logits_agreement(
            np.asarray(logits), plain,
            float(cell.config["logit_tolerance" + suffix]))
        limit = float(cell.config["routing_margin_tolerance" + suffix])
        checks["routing" + suffix] = {
            "ok": margin <= limit, "margin": margin,
            "layers": sorted(routing), "compared": win.compared(margin, "<=", limit)}
    return checks


def run(cell: Cell, devices: list, start_wall: float) -> Observed:
    from fast_autoaugment_tpu.core.checkpoint import read_metadata
    from fast_autoaugment_tpu.core.compilecache import configure_compile_cache
    from fast_autoaugment_tpu.core.config import Config
    from fast_autoaugment_tpu.core.resilience import (
        PreemptedError,
        clear_preemption,
    )
    from fast_autoaugment_tpu.data.datasets import load_dataset
    from fast_autoaugment_tpu.parallel.mesh import make_mesh
    from fast_autoaugment_tpu.train.trainer import train_and_eval

    traffic = cell.traffic
    configure_compile_cache()
    dataroot = os.path.join(cell.work, "data")
    wrote = write_fixture(dataroot, cell.fixture, cell.seed)
    conf = Config(cell.conf_dict())
    mesh = make_mesh(devices)
    ckpt_dir = os.path.join(cell.work, "ckpt")
    shutil.rmtree(ckpt_dir, ignore_errors=True)
    save_path = os.path.join(ckpt_dir, "model.msgpack")
    entry_args = dict(traffic.get("entry_args") or {})
    steps_per_dispatch = int(entry_args.get(
        "steps_per_dispatch",
        inspect.signature(train_and_eval).parameters["steps_per_dispatch"].default))

    ahead = ComparisonsAhead(cell, conf, int(traffic["reference_sequences"]),
                             wrote["length"])
    beat = train._Beat(cell, devices, mesh, start_wall)
    counting = CountersAtTheOpening(beat, ahead)
    diverged = None
    clear_preemption()
    try:
        train_and_eval(conf, dataroot, save_path=save_path, mesh=mesh,
                       seed=cell.seed, heartbeat=counting,
                       evaluation_interval=int(traffic["evaluation_interval"]),
                       **entry_args)
        raise RuntimeError("the trainer finished its epochs before the "
                           "window closed: the configuration's horizon is "
                           "shorter than set-up plus window")
    except PreemptedError:
        pass
    except RuntimeError as e:
        if "diverged" not in str(e):
            raise
        diverged = str(e)
    finally:
        if beat.tracer is not None and beat.tracer.running:
            beat.tracer.stop()
        clear_preemption()

    global_batch = int(conf["batch"]) * len(devices)
    tokens_a_step = global_batch * wrote["length"]
    checks: dict[str, dict] = {}
    if diverged or beat.state != "closed":
        steps = 0 if beat.d0 is None else (beat.last_count - beat.d0) * steps_per_dispatch
        checks["finite_loss"] = {"ok": False, "why": diverged or
                                 f"window state {beat.state!r} at exit"}
        return Observed(
            cell=cell, devices=devices, end_to_end={}, window_s=0.0,
            attempted=steps, failed=steps, checks=checks,
            compile_stats=beat.compile_stats,
            memory_peak_bytes=memory_peak_bytes(devices))

    window_s = beat.t1 - beat.t0
    steps = (beat.d1 - beat.d0) * steps_per_dispatch
    rate = steps * global_batch / window_s / len(devices)
    counted = counting.counted_since(tokens_a_step)
    checks["finite_loss"] = {"ok": True, "fixture": wrote,
                             "tokens_per_s_per_chip": rate * wrote["length"],
                             "counters": counted}
    checks["no_compile_in_window"] = train.no_compile_check(beat)

    # -- outside the window: the weights the window ended on ------------
    meta = read_metadata(save_path) or {}
    checks["step_counter"] = train.step_counter_check(
        meta, (beat.last_count - beat.first_count) * steps_per_dispatch)
    checks["learned"] = learned_check(meta, wrote["ids"],
                                      float(traffic["loss_margin"]))

    ids = load_dataset(conf["dataset"], dataroot)[1].images[
        :int(traffic["reference_sequences"])]
    checks.update(reference_checks(cell, ahead, checkpoint_params(save_path), ids))

    return Observed(
        cell=cell, devices=devices,
        end_to_end={"train_images_per_s": rate, "setup_s": beat.setup_s},
        window_s=window_s, attempted=steps, failed=0, checks=checks,
        compile_stats=beat.compile_stats,
        memory_peak_bytes=beat.memory_peak,
        work=dict(counted, images_per_s_per_chip=rate, passes="train",
                  tokens_a_step=tokens_a_step),
        step_program=traffic["step_program"],
        trace_dir=beat.tracer.directory if beat.tracer else None,
        host_spans=beat.host_spans,
        marker_perf=beat.tracer.marker_perf if beat.tracer else None)
