"""Headline benchmark: WRN-40-2 CIFAR-10 training throughput per chip.

Measures the full production train step — on-device fa_reduced_cifar10
policy augmentation (493 sub-policies as a tensor), random crop/flip,
normalize, cutout-16, forward/backward with global-batch BN, non-BN
weight decay, grad clip, SGD-nesterov, cosine+warmup LR — at the
reference's headline config (``confs/wresnet40x2_cifar.yaml``: batch
128 per device).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "mfu",
"images_per_sec_hostfeed", "contention", "tta_trials_per_sec", ...,
"platform", "device_kind", "device_count"}.  Every line names the
device JAX ran it on: there is no fallback — on a machine without a
chip the numbers are CPU numbers and say so (`platform: "cpu"`,
`vs_baseline`/`mfu` null), and a sub-bench that raises fails the run
(non-zero exit, no JSON line).  Every artifact is loadavg-stamped at
capture start (`contention`) and carries the phase-2 scheduler
throughput at candidate-batch K in {1, 4, 16} (`tta_trials_per_sec`;
see bench_tta_scheduler).

Baseline: the reference pipeline (PyTorch + 8 PIL CPU workers per GPU)
sustains roughly 1500 images/s/GPU on a V100-class device for WRN-40-2
CIFAR-10 (its 3.5 GPU-hour / 200-epoch budget on this config implies
the low thousands; no exact number is published — README.md:16).
vs_baseline = value / 1500 (a bracket); `mfu` — model FLOPs utilization
from the compiled step's XLA cost analysis against the chip's peak —
is the defensible headline on TPU.

Two throughput numbers are measured:
- `value` (headline): device-resident batch, steady-state step rate —
  pure device throughput of the fused train step;
- `images_per_sec_hostfeed`: fresh batches flow through the real host
  pipeline (`train_batches` + background `prefetch`) every step, i.e.
  end-to-end including the host feed path.
"""

import json
import os
import sys
import time

import numpy as np

REFERENCE_IMAGES_PER_SEC = 1500.0
BATCH_PER_DEVICE = max(1, int(os.environ.get("FAA_BENCH_BATCH", 128)))
# floors: warmup 0 would put the multi-minute first compile inside the
# timed loop and silently wreck the headline number
WARMUP_STEPS = max(1, int(os.environ.get("FAA_BENCH_WARMUP", 5)))
MEASURE_STEPS = max(1, int(os.environ.get("FAA_BENCH_STEPS", 30)))
#  default: cpu-count-gated (docs/loader_bench.md — depth >1 hurts on a
#  1-core host); override with FAA_BENCH_PREFETCH
_env_depth = os.environ.get("FAA_BENCH_PREFETCH")
PREFETCH_DEPTH = max(1, int(_env_depth)) if _env_depth else None

# peak dense bf16 FLOP/s per chip, keyed by the exact `device_kind` JAX
# reports (source: Google Cloud documentation "TPU v5e", 197 TFLOP/s —
# /opt/skills/guides/on-chip-measurement §3).  A TPU that is not in the
# table is an error, not a default: add its kind with its source.
_PEAK_FLOPS_BF16 = {
    "TPU v5 lite": 197e12,
}


def _log(msg):
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def host_contention_stamp() -> dict:
    """Load/contention provenance for a bench artifact.

    A number captured while the host was busy must say so in the
    artifact itself.  Every bench JSON carries the 1/5/15-minute load
    averages, the core count and the process count AT CAPTURE START,
    plus a ``contended`` verdict (pre-existing 1-minute load above 75%
    of the cores).  Set
    ``FAA_BENCH_REQUIRE_QUIET=1`` to make the bench REFUSE to run
    (exit 3) instead of merely flagging.
    """
    stamp: dict = {"cpu_count": os.cpu_count()}
    try:
        la1, la5, la15 = os.getloadavg()
        stamp["loadavg_1m"] = round(la1, 2)
        stamp["loadavg_5m"] = round(la5, 2)
        stamp["loadavg_15m"] = round(la15, 2)
    except OSError:  # not available on this platform
        stamp["loadavg_1m"] = stamp["loadavg_5m"] = stamp["loadavg_15m"] = None
    try:
        stamp["process_count"] = sum(
            1 for d in os.listdir("/proc") if d.isdigit())
    except OSError:
        stamp["process_count"] = None
    la1 = stamp["loadavg_1m"]
    stamp["contended"] = bool(
        la1 is not None and la1 > 0.75 * (stamp["cpu_count"] or 1))
    return stamp


def refuse_or_flag_contention(stamp: dict) -> dict:
    """Exit under FAA_BENCH_REQUIRE_QUIET on a busy host, else annotate."""
    if not stamp.get("contended"):
        return stamp
    msg = (f"host is contended at capture start: loadavg_1m="
           f"{stamp['loadavg_1m']} on {stamp['cpu_count']} core(s), "
           f"{stamp['process_count']} processes")
    if os.environ.get("FAA_BENCH_REQUIRE_QUIET"):
        _log(f"REFUSING to bench ({msg}); unset FAA_BENCH_REQUIRE_QUIET "
             "to capture anyway (the artifact would be flagged)")
        sys.exit(3)
    _log(f"WARNING: {msg} — artifact will be flagged contended=true; do "
         "not commit it as an official number")
    stamp["note"] = ("captured under host contention — timings are "
                     "unreliable; not an official number")
    return stamp


def watchdog_stamp(observed_walls, fires: int = 0,
                   label: str = "dispatch") -> dict:
    """Shadow-watchdog provenance for a bench artifact.

    Feeds the bench's observed per-dispatch walls through the REAL
    auto-mode EMA (``core/watchdog.py``) and stamps the deadline a
    ``--watchdog auto`` run would settle at, alongside the fire count
    (0 for an unmonitored bench).  With this next to the contention
    stamp, a BENCH artifact can distinguish a hang (deadline would
    fire) from a straggler (wall above EMA, below deadline) after the
    fact."""
    from fast_autoaugment_tpu.core.watchdog import DispatchWatchdog

    walls = [float(w) for w in observed_walls if w and w > 0]
    stamp = {"watchdog_fires": int(fires)}
    if not walls:
        stamp["watchdog_deadline_sec"] = None
        return stamp
    wd = DispatchWatchdog("auto")
    for w in walls:
        wd.observe(label, w)
    stamp["watchdog_deadline_sec"] = round(wd.deadline(label), 6)
    stamp["watchdog_ema_sec"] = round(wd.ema(label) or 0.0, 6)
    stamp["watchdog_max_observed_sec"] = round(max(walls), 6)
    return stamp


def compile_cache_stamp() -> dict:
    """The unified ``compile_cache`` block every bench JSON line
    carries: persistent-cache dir/hit/miss counts plus per-label
    first-call (compile) seconds through the seam — ONE schema across
    ``bench.py`` and the ``tools/bench_*.py`` siblings (the comparable
    record the ad-hoc per-tool ``compile_*_sec`` keys never were)."""
    from fast_autoaugment_tpu.core.compilecache import compile_cache_stats

    return compile_cache_stats()


#: version of the unified telemetry_stamp() block — bump on any key
#: rename/removal so cross-round bench JSON comparisons can gate on it
TELEMETRY_STAMP_SCHEMA_VERSION = 1


def telemetry_stamp(observed_walls=(), *, fires: int = 0,
                    label: str = "dispatch",
                    contention: dict | None = None,
                    watchdog: dict | None = None) -> dict:
    """THE unified provenance block for a bench JSON line.

    One schema (``schema_version`` + ``contention`` + ``watchdog`` +
    ``compile_cache`` + the telemetry registry's counters) across
    ``bench.py`` and every ``tools/bench_*.py`` sibling — each tool
    used to re-implement its own stamp block from the individual
    helpers, which is exactly how schemas drift.  Splat the result into
    the artifact (``row.update(telemetry_stamp(...))``): the historical
    top-level keys (``contention``/``watchdog``/``compile_cache``) keep
    their names and shapes.

    `observed_walls`/`fires`/`label` feed the shadow-watchdog stamp
    (or pass a pre-built `watchdog` dict — per-row sweeps that already
    stamped a per-config deadline keep it); `contention` reuses a stamp
    captured earlier (benches capture it BEFORE compiling so their own
    load doesn't pollute the 1-minute average) or captures one now."""
    from fast_autoaugment_tpu.core import telemetry

    return {
        "schema_version": TELEMETRY_STAMP_SCHEMA_VERSION,
        "contention": (contention if contention is not None
                       else host_contention_stamp()),
        "watchdog": (watchdog if watchdog is not None
                     else watchdog_stamp(observed_walls, fires=fires,
                                         label=label)),
        "compile_cache": compile_cache_stamp(),
        "telemetry_counters": telemetry.registry().counters_snapshot(),
    }


def vs_baseline(images_per_sec: float, platform: str) -> float | None:
    """Ratio against the reference-pipeline estimate — on a TPU only.
    A CPU run has no meaningful ratio against a GPU-class 1500 img/s
    baseline, so anything but ``platform == "tpu"`` reports None."""
    if platform != "tpu":
        return None
    return round(images_per_sec / REFERENCE_IMAGES_PER_SEC, 3)


def _chip_peak_flops(device) -> float | None:
    """Peak bf16 FLOP/s for this chip by its exact ``device_kind``.
    None on the CPU (MFU against a TPU peak is meaningless there); a
    TPU kind missing from :data:`_PEAK_FLOPS_BF16` raises."""
    if device.platform == "cpu":
        return None
    try:
        return _PEAK_FLOPS_BF16[device.device_kind]
    except KeyError:
        raise KeyError(
            f"no peak FLOP/s on record for device_kind "
            f"{device.device_kind!r} (platform {device.platform!r}) — add "
            "it to bench._PEAK_FLOPS_BF16 with its source") from None


def _step_flops(lowered_compiled) -> float | None:
    """FLOPs of one compiled step from XLA's cost analysis.

    Under SPMD partitioning these are PER-DEVICE flops (the analysis is
    of the partitioned module), so MFU = flops * step_rate / chip_peak
    with no extra device division (verified empirically: a 4-way-sharded
    matmul reports 1/4 the unsharded flops)."""
    try:
        cost = lowered_compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        flops = float(cost.get("flops", 0.0))
        return flops if flops > 0 else None
    except Exception as e:  # noqa: BLE001 — backend-dependent API
        _log(f"cost_analysis unavailable: {e}")
        return None


def bench_tta_scheduler(ks=(1, 4, 16), trials_per_k=None) -> dict:
    """Phase-2 scheduler throughput: TTA trials/sec at candidate-batch K.

    Runs a faithful miniature of `search/driver.py` phase 2 — real
    in-tree TPE proposals (`ask(K)`/`tell_batch`), real policy
    decode/tensorize, the real compiled TTA step (`make_tta_step`,
    candidate axis vmapped for K>1), and the real per-round fsync
    trial-log persist — at a deliberately tiny probe shape
    (`FAA_BENCH_TTA_MODEL` @ `FAA_BENCH_TTA_IMG` px, batch
    `FAA_BENCH_TTA_BATCH`, 1 TTA draw) so the FIXED per-trial costs the
    batched scheduler amortizes (dispatch, host sync, fsync persist,
    proposal overhead) are visible next to the device math.  K=1 is the
    sequential scheduler code path (`suggest`/`tell`, one program per
    trial); K>1 evaluates K trials per device program.

    On a TPU the same amortization applies to a device that finishes
    the math orders of magnitude faster, PLUS the K*P*B batch actually
    fills the MXU — so the CPU-measured speedup is a LOWER bound on the
    scheduling win, not a chip throughput claim.  The headline train
    bench above stays the chip-throughput number.
    """
    import tempfile

    import jax
    import jax.numpy as jnp

    from fast_autoaugment_tpu.models import get_model
    from fast_autoaugment_tpu.policies.archive import (
        policy_decoder,
        policy_to_tensor,
    )
    from fast_autoaugment_tpu.search.driver import (
        make_search_space,
        write_json_atomic,
    )
    from fast_autoaugment_tpu.search.tpe import TPE
    from fast_autoaugment_tpu.search.tta import (
        eval_tta,
        eval_tta_batched,
        make_tta_step,
    )

    model_type = os.environ.get("FAA_BENCH_TTA_MODEL", "wresnet10_1")
    img = int(os.environ.get("FAA_BENCH_TTA_IMG", 8))
    batch = int(os.environ.get("FAA_BENCH_TTA_BATCH", 1))
    num_policy, num_op, n_sub = 1, 1, 1
    if trials_per_k is None:
        trials_per_k = max(
            max(ks), int(os.environ.get("FAA_BENCH_TTA_TRIALS", 192)))
    repeats = max(1, int(os.environ.get("FAA_BENCH_TTA_REPEATS", 3)))

    model = get_model({"type": model_type}, 10)
    rng = np.random.default_rng(0)
    images = rng.integers(0, 256, (batch, img, img, 3), dtype=np.uint8)
    labels = rng.integers(0, 10, (batch,), np.int32)
    mask = np.ones(batch, np.float32)
    batches = [{"x": jnp.asarray(images), "y": jnp.asarray(labels),
                "m": jnp.asarray(mask)}]
    variables = model.init(
        jax.random.PRNGKey(0), jnp.zeros((2, img, img, 3), jnp.float32),
        train=False)
    params = variables["params"]
    batch_stats = variables.get("batch_stats", {})
    space = make_search_space(n_sub, num_op)
    tmpdir = tempfile.mkdtemp(prefix="faa_tta_bench_")
    trials_path = os.path.join(tmpdir, "search_trials.json")
    key_fold = jax.random.PRNGKey(7)

    def run_rounds(k, n_trials, step):
        """The phase-2 inner loop at candidate-batch k; returns seconds."""
        tpe = TPE(space, seed=0, n_startup=5)
        trial_log = []
        t0 = time.perf_counter()
        done = 0
        while done < n_trials:
            if k == 1:
                proposal = tpe.suggest()
                policy_t = jnp.asarray(policy_to_tensor(
                    policy_decoder(proposal, n_sub, num_op)))
                m = eval_tta(step, params, batch_stats, batches, policy_t,
                             jax.random.fold_in(key_fold, done))
                tpe.tell(proposal, m["top1_valid"])
                trial_log.append((proposal, m["top1_valid"]))
            else:
                proposals = tpe.ask(k)
                policies_t = jnp.asarray(np.stack([
                    np.asarray(policy_to_tensor(
                        policy_decoder(p, n_sub, num_op)), np.float32)
                    for p in proposals
                ]))
                keys = jnp.stack([jax.random.fold_in(key_fold, done + i)
                                  for i in range(k)])
                ms = eval_tta_batched(step, params, batch_stats, batches,
                                      policies_t, keys)
                rewards = [m["top1_valid"] for m in ms]
                tpe.tell_batch(proposals, rewards)
                trial_log.extend(zip(proposals, rewards))
            # the driver's per-round durability write (fsync + rename)
            write_json_atomic(trials_path, {"0": trial_log})
            done += k
        return time.perf_counter() - t0, done

    out = {"probe": {"model": model_type, "image": img, "batch": batch,
                     "num_policy": num_policy, "num_sub": n_sub,
                     "trials_per_k": trials_per_k},
           "trials_per_sec": {}}
    for k in ks:
        t_c = time.perf_counter()
        step = make_tta_step(model, num_policy=num_policy, cutout_length=0,
                             num_candidates=None if k == 1 else k)
        # warm-up round: compile lands here, outside the timed loop
        run_rounds(k, k, step)
        compile_s = time.perf_counter() - t_c
        # best of `repeats`: the least-contended window is the honest
        # scheduler rate on a shared host (the stamp records the load)
        rate, done = 0.0, 0
        for _ in range(repeats):
            dt, done = run_rounds(k, trials_per_k, step)
            rate = max(rate, done / dt)
        out["trials_per_sec"][str(k)] = round(rate, 2)
        _log(f"tta scheduler K={k}: {rate:.1f} trials/s best-of-{repeats} "
             f"({done} trials/repeat; compile+warm {compile_s:.1f}s)")
    base = out["trials_per_sec"].get("1")
    top = out["trials_per_sec"].get(str(max(ks)))
    if base and top:
        out["speedup_max_k_vs_1"] = round(top / base, 2)
    return out


def bench_fold_stack(num_folds=5, steps=None) -> dict:
    """Phase-1 scheduler throughput: fold-train steps/sec at
    ``--fold-stack {0, K}``.

    Runs a faithful miniature of phase-1 fold pretraining — the real
    jitted train step (`make_train_step`) vs the real fold-stacked step
    (`make_stacked_train_step`, K whole learner replicas vmapped into
    one program) on K independent states — at a tiny probe shape
    (`FAA_BENCH_FS_MODEL` @ `FAA_BENCH_FS_IMG` px, batch
    `FAA_BENCH_FS_BATCH`) so the per-step FIXED costs the stacked
    scheduler amortizes (K per-fold program dispatches per step -> one)
    are visible next to the device math.  The unit is FOLD-steps/sec:
    one stacked call counts K.  On a TPU the same amortization applies
    PLUS the K-model batch actually fills the MXU — the CPU number is a
    lower bound on the scheduling win, exactly as `bench_tta_scheduler`
    is for phase 2.
    """
    import jax
    import jax.numpy as jnp

    from fast_autoaugment_tpu.models import get_model
    from fast_autoaugment_tpu.ops.optim import build_optimizer
    from fast_autoaugment_tpu.train.steps import (
        create_train_state,
        make_stacked_train_step,
        make_train_step,
        stack_states,
    )

    model_type = os.environ.get("FAA_BENCH_FS_MODEL", "wresnet10_1")
    img = int(os.environ.get("FAA_BENCH_FS_IMG", 8))
    batch = int(os.environ.get("FAA_BENCH_FS_BATCH", 4))
    if steps is None:
        steps = max(1, int(os.environ.get("FAA_BENCH_FS_STEPS", 30)))
    repeats = max(1, int(os.environ.get("FAA_BENCH_FS_REPEATS", 3)))

    model = get_model({"type": model_type}, 10)
    opt_conf = {"type": "sgd", "decay": 2e-4, "clip": 5.0, "momentum": 0.9,
                "nesterov": True}
    sample = jnp.zeros((2, img, img, 3), jnp.float32)
    kw = dict(num_classes=10, cutout_length=0, use_policy=False)
    rng = np.random.default_rng(0)
    images = rng.integers(0, 256, (num_folds, batch, img, img, 3),
                          dtype=np.uint8)
    labels = rng.integers(0, 10, (num_folds, batch), np.int32)
    pol = jnp.zeros((1, 1, 3), jnp.float32)
    keys = jnp.stack([jax.random.PRNGKey(k) for k in range(num_folds)])
    active = jnp.ones((num_folds,), jnp.float32)

    def fresh_states():
        opt = build_optimizer(opt_conf, lambda s: 0.05)
        return [create_train_state(model, opt, jax.random.PRNGKey(k), sample,
                                   use_ema=False) for k in range(num_folds)]

    out = {"probe": {"model": model_type, "image": img, "batch": batch,
                     "num_folds": num_folds, "steps": steps},
           "steps_per_sec": {}}

    # sequential: one program per (fold, step) — today's phase-1 loop
    opt = build_optimizer(opt_conf, lambda s: 0.05)
    seq_step = make_train_step(model, opt, **kw)
    states = fresh_states()
    xs = [jnp.asarray(images[k]) for k in range(num_folds)]
    ys = [jnp.asarray(labels[k]) for k in range(num_folds)]
    for k in range(num_folds):  # compile + warm outside the timed loop
        states[k], _ = seq_step(states[k], xs[k], ys[k], pol, keys[k])
    jax.block_until_ready(states[0].params)
    rate = 0.0
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(steps):
            for k in range(num_folds):
                states[k], _ = seq_step(states[k], xs[k], ys[k], pol, keys[k])
        jax.block_until_ready(states[0].params)
        rate = max(rate, steps * num_folds / (time.perf_counter() - t0))
    out["steps_per_sec"]["0"] = round(rate, 2)
    _log(f"fold-stack K=0 (sequential): {rate:.1f} fold-steps/s "
         f"best-of-{repeats}")

    # stacked: K folds per program — the --fold-stack K scheduler
    opt = build_optimizer(opt_conf, lambda s: 0.05)
    st_step = make_stacked_train_step(model, opt, **kw)
    stacked = stack_states(fresh_states())
    xst, yst = jnp.asarray(images), jnp.asarray(labels)
    stacked, _ = st_step(stacked, xst, yst, pol, keys, active)
    jax.block_until_ready(stacked.params)
    rate = 0.0
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(steps):
            stacked, _ = st_step(stacked, xst, yst, pol, keys, active)
        jax.block_until_ready(stacked.params)
        rate = max(rate, steps * num_folds / (time.perf_counter() - t0))
    out["steps_per_sec"][str(num_folds)] = round(rate, 2)
    _log(f"fold-stack K={num_folds} (stacked): {rate:.1f} fold-steps/s "
         f"best-of-{repeats}")
    base = out["steps_per_sec"]["0"]
    top = out["steps_per_sec"][str(num_folds)]
    if base and top:
        out["speedup_stacked_vs_sequential"] = round(top / base, 2)
    return out


def _dispatch_probe_model():
    """Conv-free probe for `bench_step_dispatch`: dense + batch-norm.

    The dispatch bench measures the per-step FIXED costs (host gather,
    device_put, program launch, metric-sum dispatches) that multi-step
    fusion removes, so the probe's device math must be small enough not
    to drown them — AND must avoid convolutions, whose BACKWARD pass
    inside an XLA:CPU while loop hits a ~3-4x slow kernel path that
    would turn the CPU measurement into a conv-kernel artifact instead
    of a dispatch measurement (`train/steps.py::default_dispatch_unroll`
    documents the pathology; TPU scans of conv models are the standard
    pjit-trainer shape and unaffected).  Set FAA_BENCH_SD_MODEL to a
    registry model (e.g. wresnet10_1) to measure a CNN probe instead —
    on CPU that number understates the win for exactly this reason.
    """
    import flax.linen as nn
    import jax.numpy as jnp

    class DispatchProbe(nn.Module):
        features: int = 32

        @nn.compact
        def __call__(self, x, train: bool = False):
            x = x.reshape((x.shape[0], -1))
            x = nn.Dense(self.features)(x)
            x = nn.BatchNorm(use_running_average=not train,
                             momentum=0.9)(x)
            x = nn.relu(x)
            return nn.Dense(10)(x)

    return DispatchProbe()


def bench_step_dispatch(ns=(1, 8, 32), steps=None,
                        telemetry_compare: bool = False) -> dict:
    """Train-step dispatch throughput: `train_steps_per_sec` at
    ``--steps-per-dispatch N`` with the device cache vs the host feed.

    Runs a faithful miniature of the trainer's inner loop — the real
    jitted step (`make_train_step`) fed fresh host batches through
    `train_batches` + `shard_batch`, with the trainer's per-step
    metric-sum accumulation (one fancy-gather + H2D copy + dispatch +
    metric adds per step, today's path) — against the real multi-step
    program (`make_multistep_train_step` over a `DeviceCache`: one
    int32 index matrix + ONE dispatch + one metric add per N steps).
    The probe model is deliberately dispatch-bound and conv-free
    (see `_dispatch_probe_model`; FAA_BENCH_SD_MODEL overrides), at
    `FAA_BENCH_SD_IMG` px / batch `FAA_BENCH_SD_BATCH`.  On a TPU the
    same amortization applies on top of device math the MXU finishes
    faster — the CPU number measures the scheduling win, not chip
    throughput, exactly as `bench_fold_stack` does for fold stacking.
    Per-(N, cache) compile seconds ride in the JSON line.
    """
    import jax
    import jax.numpy as jnp

    from fast_autoaugment_tpu.core.metrics import Accumulator
    from fast_autoaugment_tpu.data.datasets import ArrayDataset
    from fast_autoaugment_tpu.data.pipeline import (
        DeviceCache,
        train_batches,
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

    model_type = os.environ.get("FAA_BENCH_SD_MODEL", "linear")
    img = int(os.environ.get("FAA_BENCH_SD_IMG", 8))
    batch = int(os.environ.get("FAA_BENCH_SD_BATCH", 4))
    if steps is None:
        # divisible by every N so all configs run the same step count
        steps = max(max(ns), int(os.environ.get("FAA_BENCH_SD_STEPS", 192)))
        steps -= steps % max(ns)
    repeats = max(1, int(os.environ.get("FAA_BENCH_SD_REPEATS", 3)))

    mesh = make_mesh()
    model = (_dispatch_probe_model() if model_type == "linear"
             else get_model({"type": model_type}, 10))
    # conv-free probe: the rolled scan is the fast CPU shape (and the
    # TPU production shape); registry CNN probes take the trainer's
    # default_dispatch_unroll (full unroll on CPU — conv-backward-in-
    # loop pathology, _dispatch_probe_model docstring)
    unroll = 1 if model_type == "linear" else None
    opt_conf = {"type": "sgd", "decay": 2e-4, "clip": 5.0, "momentum": 0.9,
                "nesterov": True}
    kw = dict(num_classes=10, cutout_length=0, use_policy=False)
    sample = jnp.zeros((2, img, img, 3), jnp.float32)
    rng = np.random.default_rng(0)
    n_examples = max(256, 2 * batch)
    ds = ArrayDataset(
        rng.integers(0, 256, (n_examples, img, img, 3), dtype=np.uint8),
        rng.integers(0, 10, (n_examples,), np.int32), 10)
    rep = replicated(mesh)
    pol = jax.device_put(jnp.zeros((1, 1, 3), jnp.float32), rep)
    key = jax.device_put(jax.random.PRNGKey(0), rep)

    def fresh_state():
        opt = build_optimizer(opt_conf, lambda s: 0.05)
        state = create_train_state(model, opt, jax.random.PRNGKey(0), sample,
                                   use_ema=False)
        # mesh-commit: uncommitted state + committed cache knocks every
        # dispatch off the C++ fast path (make_multistep_train_step)
        return jax.device_put(state, rep)

    out = {"probe": {"model": model_type, "image": img, "batch": batch,
                     "steps": steps, "scan_unroll": unroll or "default"},
           "train_steps_per_sec": {}, "compile_sec": {}}

    def host_epoch(state, step_fn, n_steps):
        acc = Accumulator()
        done = 0
        while done < n_steps:  # cycle fresh epochs until n_steps consumed
            for b in train_batches(ds, None, batch, epoch=done):
                b = shard_batch(mesh, {"x": b[0], "y": b[1]})
                state, metrics = step_fn(state, b["x"], b["y"], pol, key)
                acc.add_dict(metrics)
                done += 1
                if done >= n_steps:
                    break
        return state

    # host-fed N=1: today's loop — gather + device_put + dispatch per step
    opt = build_optimizer(opt_conf, lambda s: 0.05)
    seq_step = make_train_step(model, opt, **kw)
    t_c = time.perf_counter()
    state = host_epoch(fresh_state(), seq_step, 1)  # compile + warm
    jax.block_until_ready(state.params)
    out["compile_sec"]["hostfeed_n1"] = round(time.perf_counter() - t_c, 2)
    rate = 0.0
    for _ in range(repeats):
        state = fresh_state()
        t0 = time.perf_counter()
        state = host_epoch(state, seq_step, steps)
        jax.block_until_ready(state.params)
        rate = max(rate, steps / (time.perf_counter() - t0))
    out["train_steps_per_sec"]["hostfeed_n1"] = round(rate, 2)
    _log(f"step dispatch host-fed N=1: {rate:.1f} steps/s best-of-{repeats}")

    # device cache at each N: one dispatch per N steps, index-fed
    opt = build_optimizer(opt_conf, lambda s: 0.05)
    body = make_train_step_body(model, opt, **kw)
    cache = DeviceCache(ds, mesh)
    for n in ns:
        multi = make_multistep_train_step(body, steps_per_dispatch=n,
                                          unroll=unroll)

        def cache_epoch(state, n_steps, n=n, multi=multi):
            acc = Accumulator()
            done = 0
            while done < n_steps:
                mat = train_index_matrix(np.arange(n_examples), batch,
                                         epoch=done)
                for lo in range(0, len(mat) - len(mat) % n, n):
                    idx = place_index_matrix(mesh, mat[lo:lo + n])
                    state, metrics = multi(state, cache.images, cache.labels,
                                           idx, pol, key)
                    acc.add_dict(metrics)
                    done += n
                    if done >= n_steps:
                        break
            return state

        t_c = time.perf_counter()
        state = cache_epoch(fresh_state(), n)  # compile + warm
        jax.block_until_ready(state.params)
        out["compile_sec"][f"cache_n{n}"] = round(time.perf_counter() - t_c, 2)
        rate = 0.0
        for _ in range(repeats):
            state = fresh_state()
            t0 = time.perf_counter()
            state = cache_epoch(state, steps)
            jax.block_until_ready(state.params)
            rate = max(rate, steps / (time.perf_counter() - t0))
        out["train_steps_per_sec"][f"cache_n{n}"] = round(rate, 2)
        _log(f"step dispatch cache N={n}: {rate:.1f} steps/s "
             f"best-of-{repeats}")

    base = out["train_steps_per_sec"].get("hostfeed_n1")
    top = out["train_steps_per_sec"].get(f"cache_n{max(ns)}")
    if base and top:
        out["speedup_cache_max_n_vs_hostfeed"] = round(top / base, 2)

    # telemetry on-vs-off comparison row (the observability acceptance
    # bound): the SAME cache_nN loop with telemetry fully armed —
    # journal into a scratch dir, one span (registry histogram +
    # rate-bounded JSONL event) per dispatch, exactly the per-dispatch
    # cost the trainer's _monitored_dispatch seam pays with --telemetry
    # on — measured as PAIRED ALTERNATING epochs (off, on, off, on, …)
    # with per-arm medians: this host's run-to-run drift (~±2-3%) would
    # otherwise swamp a microsecond-scale per-dispatch delta.  Overhead
    # must stay <= 1% steps/s (docs/OBSERVABILITY.md "Overhead").
    if telemetry_compare:
        import shutil
        import statistics
        import tempfile

        from fast_autoaugment_tpu.core import telemetry

        was_on = telemetry.journal_active()
        tmp = None
        if not was_on:
            tmp = tempfile.mkdtemp(prefix="faa-bench-telemetry-")
            telemetry.enable_telemetry(tmp)  # full default config
        pairs = max(5, repeats)
        out["telemetry_comparison"] = {"pairs": pairs, "steps": steps}
        try:
            for n in ns:
                multi = make_multistep_train_step(
                    body, steps_per_dispatch=n, unroll=unroll)

                def one_epoch(state, n_steps, with_span, n=n, multi=multi):
                    acc = Accumulator()
                    done = 0
                    while done < n_steps:
                        mat = train_index_matrix(np.arange(n_examples),
                                                 batch, epoch=done)
                        for lo in range(0, len(mat) - len(mat) % n, n):
                            idx = place_index_matrix(mesh, mat[lo:lo + n])
                            if with_span:
                                with telemetry.span("train_dispatch",
                                                    step=done):
                                    state, metrics = multi(
                                        state, cache.images, cache.labels,
                                        idx, pol, key)
                            else:
                                state, metrics = multi(
                                    state, cache.images, cache.labels,
                                    idx, pol, key)
                            acc.add_dict(metrics)
                            done += n
                            if done >= n_steps:
                                break
                    return state

                state = one_epoch(fresh_state(), n, True)  # warm
                jax.block_until_ready(state.params)
                rates = {False: [], True: []}
                for p in range(pairs):
                    # alternate the within-pair order: process state
                    # (allocator, caches) drifts monotonically, so a
                    # fixed off-then-on order reads that drift as
                    # telemetry overhead
                    order = (False, True) if p % 2 == 0 else (True, False)
                    for with_span in order:
                        state = fresh_state()
                        t0 = time.perf_counter()
                        state = one_epoch(state, steps, with_span)
                        jax.block_until_ready(state.params)
                        rates[with_span].append(
                            steps / (time.perf_counter() - t0))
                off = statistics.median(rates[False])
                on = statistics.median(rates[True])
                out["telemetry_comparison"][f"cache_n{n}"] = {
                    "steps_per_sec_off": round(off, 2),
                    "steps_per_sec_on": round(on, 2),
                    "overhead_frac": round(1.0 - on / off, 4),
                }
                _log(f"step dispatch cache N={n} telemetry off/on "
                     f"(median of {pairs} alternating pairs): "
                     f"{off:.1f} / {on:.1f} steps/s "
                     f"({(1.0 - on / off) * 100:+.2f}%)")
        finally:
            if not was_on:
                telemetry._disable_for_tests()  # detach the scratch journal
                if tmp:
                    shutil.rmtree(tmp, ignore_errors=True)
    # per-config shadow-watchdog stamp from the implied per-dispatch
    # wall (a cache_nN dispatch advances N steps)
    out["watchdog"] = {
        cfg: watchdog_stamp([int(cfg.rsplit("n", 1)[1]) / rate], label=cfg)
        for cfg, rate in out["train_steps_per_sec"].items() if rate
    }
    return out


def main():
    # stamp BEFORE any compile ramps our own load into the 1-min average
    contention = refuse_or_flag_contention(host_contention_stamp())
    from fast_autoaugment_tpu.core.compilecache import configure_compile_cache
    from fast_autoaugment_tpu.parallel.mesh import device_stamp

    configure_compile_cache()
    if "--dispatch-only" in sys.argv:
        # `make bench-dispatch`: just the step-dispatch/device-cache
        # sweep, one JSON line (same stamp discipline as the headline),
        # plus the telemetry on-vs-off comparison row (the <=1% overhead
        # bound — docs/OBSERVABILITY.md)
        sd = bench_step_dispatch(telemetry_compare=True)
        row = {
            "metric": "train_steps_per_sec",
            "train_steps_per_sec": sd["train_steps_per_sec"],
            "telemetry_comparison": sd.get("telemetry_comparison"),
            "compile_sec": sd["compile_sec"],
            "probe": sd["probe"],
            "speedup_cache_max_n_vs_hostfeed": sd.get(
                "speedup_cache_max_n_vs_hostfeed"),
            **device_stamp(),
        }
        row.update(telemetry_stamp(contention=contention))
        # per-config shadow-watchdog detail (telemetry_stamp carries the
        # single-label stamp; the sweep's per-(N, cache) table rides on)
        row["watchdog"] = sd.get("watchdog")
        print(json.dumps(row))
        return
    out, step_times = bench_headline()
    # unified provenance block (schema_version + contention + shadow
    # watchdog + compile cache + telemetry counters) — ONE helper across
    # bench.py and every tools/bench_*.py sibling (docs/OBSERVABILITY.md)
    out.update(telemetry_stamp(step_times, label="train_step",
                               contention=contention))
    # a sub-bench that raises fails the run: no JSON line, non-zero exit
    out.update(run_sub_benches())
    out.update(device_stamp())
    print(json.dumps(out))


def bench_headline() -> tuple[dict, list[float]]:
    """The WRN-40-2 train-step measurement (module docstring): the
    headline fields, and the raw per-step times for the shadow-watchdog
    stamp."""
    import jax
    import jax.numpy as jnp

    from fast_autoaugment_tpu.models import get_model
    from fast_autoaugment_tpu.ops.optim import build_optimizer
    from fast_autoaugment_tpu.ops.schedules import build_schedule
    from fast_autoaugment_tpu.parallel.mesh import make_mesh, shard_batch
    from fast_autoaugment_tpu.policies.archive import load_policy, policy_to_tensor
    from fast_autoaugment_tpu.train.steps import create_train_state, make_train_step

    mesh = make_mesh()
    n_dev = mesh.size
    global_batch = BATCH_PER_DEVICE * n_dev

    conf = {
        "lr": 0.1, "epoch": 200,
        "lr_schedule": {"type": "cosine", "warmup": {"multiplier": 2, "epoch": 5}},
    }
    # bf16 activations (f32 params/BN) — the TPU-first precision choice
    model = get_model({"type": "wresnet40_2", "precision": "bf16"}, 10)
    optimizer = build_optimizer(
        {"type": "sgd", "decay": 2e-4, "clip": 5.0, "momentum": 0.9, "nesterov": True},
        build_schedule(conf, steps_per_epoch=50000 // global_batch,
                       world_lr_scale=float(n_dev)),
    )
    rng = jax.random.PRNGKey(0)
    sample = jnp.zeros((2, 32, 32, 3), jnp.float32)
    state = create_train_state(model, optimizer, rng, sample, use_ema=False)
    train_step = make_train_step(
        model, optimizer, num_classes=10, cutout_length=16, use_policy=True
    )

    policy = jnp.asarray(policy_to_tensor(load_policy("fa_reduced_cifar10")))
    images = np.random.default_rng(0).integers(
        0, 256, (global_batch, 32, 32, 3), dtype=np.uint8
    )
    labels = np.random.default_rng(1).integers(0, 10, (global_batch,), np.int32)
    batch = shard_batch(mesh, {"x": images, "y": labels})

    _log(f"devices={n_dev} global_batch={global_batch}; compiling train step "
         "(first TPU compile can take minutes)")
    # AOT-compile ONCE: the same executable serves warmup, the timed
    # loop and the FLOPs cost analysis (a second lower().compile() just
    # for cost_analysis would double the multi-minute TPU compile)
    t_compile = time.perf_counter()
    step_exec = train_step.lower(state, batch["x"], batch["y"], policy, rng).compile()
    compile_train_step_sec = time.perf_counter() - t_compile
    _log(f"compile: {compile_train_step_sec:.1f}s")
    for _ in range(WARMUP_STEPS):
        state, metrics = step_exec(state, batch["x"], batch["y"], policy, rng)
    jax.block_until_ready(state.params)
    _log(f"warmup done; measuring {MEASURE_STEPS} steps")

    t0 = time.perf_counter()
    for _ in range(MEASURE_STEPS):
        state, metrics = step_exec(state, batch["x"], batch["y"], policy, rng)
    jax.block_until_ready(state.params)
    dt = time.perf_counter() - t0
    images_per_sec_per_chip = MEASURE_STEPS * global_batch / dt / n_dev

    # per-step spread (a mean alone carries no sample-size signal): a
    # second pass timing each step individually
    # (block per step — slightly pessimistic vs the pipelined headline,
    # but the variance is the point, not the mean)
    step_times = []
    for _ in range(MEASURE_STEPS):
        t_s = time.perf_counter()
        state, metrics = step_exec(state, batch["x"], batch["y"], policy, rng)
        jax.block_until_ready(state.params)
        step_times.append(time.perf_counter() - t_s)
    step_time_stddev = float(np.std(step_times, ddof=1)) if len(step_times) > 1 else 0.0

    # MFU: per-device FLOPs of the whole fused step (aug+fwd/bwd+opt)
    # x step rate / chip peak
    flops = _step_flops(step_exec)
    peak = _chip_peak_flops(jax.devices()[0])
    mfu = None
    if flops and peak:
        mfu = round(flops * (MEASURE_STEPS / dt) / peak, 4)
        _log(f"per-device step flops={flops:.3e} peak={peak:.0e} mfu={mfu}")

    # end-to-end: fresh host batches through the production pipeline
    # (train_batches + threaded prefetch) — includes the host feed path
    from fast_autoaugment_tpu.data.datasets import ArrayDataset
    from fast_autoaugment_tpu.data.pipeline import prefetch, train_batches

    host_rng = np.random.default_rng(2)
    n_examples = max(global_batch * (MEASURE_STEPS + 2), global_batch)
    ds = ArrayDataset(
        host_rng.integers(0, 256, (n_examples, 32, 32, 3), dtype=np.uint8),
        host_rng.integers(0, 10, (n_examples,), dtype=np.int32), 10,
    )
    from fast_autoaugment_tpu.parallel.mesh import shard_transform

    it = prefetch(
        train_batches(ds, None, global_batch, epoch=1), depth=PREFETCH_DEPTH,
        transform=shard_transform(mesh),
    )
    b = next(it)  # warm the pipeline + any reshape paths
    state, _ = step_exec(state, b["x"], b["y"], policy, rng)
    jax.block_until_ready(state.params)
    t0 = time.perf_counter()
    hf_steps = 0
    for b in it:
        state, _ = step_exec(state, b["x"], b["y"], policy, rng)
        hf_steps += 1
        if hf_steps >= MEASURE_STEPS:
            break
    jax.block_until_ready(state.params)
    dt_hf = time.perf_counter() - t0
    hostfeed = hf_steps * global_batch / dt_hf / n_dev if hf_steps else None
    # release the worker and its buffered device-resident batches NOW,
    # not when main() returns (the generator holds up to `depth` batches
    # in HBM otherwise)
    it.close()

    out = {
        "metric": "wrn40x2_cifar10_train_images_per_sec_per_chip",
        "value": round(images_per_sec_per_chip, 1),
        "unit": "images/sec/chip",
        "vs_baseline": vs_baseline(images_per_sec_per_chip,
                                   jax.devices()[0].platform),
        "mfu": mfu,
        "images_per_sec_hostfeed": round(hostfeed, 1) if hostfeed else None,
        # first-class: the first compile is a real cost the artifact
        # should carry
        "compile_train_step_sec": round(compile_train_step_sec, 1),
        # sample-size + spread provenance: how many steps the mean
        # covers and how noisy the individually-timed steps were
        "steps_measured": MEASURE_STEPS,
        "step_time_stddev_sec": round(step_time_stddev, 6),
        "batch_per_device": BATCH_PER_DEVICE,
        "devices": n_dev,
    }
    return out, step_times


def run_sub_benches() -> dict:
    """The embedded scheduler probes, each skippable by its own
    ``FAA_BENCH_*=0`` knob.  Nothing is caught here: a probe that raises
    takes the whole run down instead of printing a null."""
    out: dict = {}
    # search-scheduler throughput: trials/sec at --trial-batch K
    # (FAA_BENCH_TTA=0 skips; see bench_tta_scheduler docstring)
    if os.environ.get("FAA_BENCH_TTA", "1") != "0":
        tta = bench_tta_scheduler()
        out["tta_trials_per_sec"] = tta["trials_per_sec"]
        out["tta_bench"] = {k: v for k, v in tta.items()
                            if k != "trials_per_sec"}

    # phase-1 scheduler throughput: fold-train steps/sec at
    # --fold-stack {0, K} (FAA_BENCH_FOLD_STACK=0 skips) — tracks the
    # fold-stacking win the way tta_trials_per_sec tracks trial batching
    if os.environ.get("FAA_BENCH_FOLD_STACK", "1") != "0":
        fs = bench_fold_stack()
        out["fold_stack_steps_per_sec"] = fs["steps_per_sec"]
        out["fold_stack_bench"] = {k: v for k, v in fs.items()
                                   if k != "steps_per_sec"}

    # step-dispatch throughput: train steps/sec at --steps-per-dispatch
    # N with/without the device cache (FAA_BENCH_STEP_DISPATCH=0 skips)
    # — tracks the host-loop-removal win the way fold_stack_steps_per_
    # sec tracks fold stacking
    if os.environ.get("FAA_BENCH_STEP_DISPATCH", "1") != "0":
        sd = bench_step_dispatch()
        out["train_steps_per_sec"] = sd["train_steps_per_sec"]
        out["step_dispatch_bench"] = {k: v for k, v in sd.items()
                                      if k != "train_steps_per_sec"}
    return out


if __name__ == "__main__":
    main()
