"""Grouped scalar-dispatch augmentation kernels (``--aug-dispatch``).

Covers the three contracts of the dispatch split:

- ``exact`` (the default) is pinned against a committed golden capture
  (``tests/data/aug_exact_golden.npz``) so a silent default flip or
  kernel drift fails loudly.  The capture was regenerated in PR 21
  under the installed JAX's default PRNG (partitionable threefry)
  after checking that the previous capture — drawn from the
  pre-grouped-kernel tree — still reproduces bit-for-bit under
  ``JAX_THREEFRY_PARTITIONABLE=0``: the kernels are unchanged, only
  the random stream moved;
- ``grouped`` is a *documented distributional deviation* with identical
  per-image marginals: stratified (per-chunk) sub-policy selection,
  exactly per-image `prob` gating — checked statistically (chi-square on
  selection counts, gate-rate preservation, within-chunk gate variety);
- where the sub-policy is already fixed per lane (single-sub policies:
  the audit, the quality-gate baseline), grouped needs no distribution
  change at all and must match exact numerically.

Tier-1 keeps only the cheap guards (the golden exact-default pin, the
grouped permutation-plumbing check, flag units); every
compile-heavy wiring/parity test and the statistical tests carry
``@pytest.mark.slow`` so the tier-1 suite stays inside its wall-clock
budget on a 1-core host (``make test`` still runs everything).
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from fast_autoaugment_tpu.ops import augment as A
from fast_autoaugment_tpu.ops.preprocess import cifar_train_batch

GOLDEN = os.path.join(os.path.dirname(__file__), "data",
                      "aug_exact_golden.npz")


def _rand_imgs(seed, b=32, h=16, w=16):
    return np.random.default_rng(seed).integers(
        0, 256, (b, h, w, 3), dtype=np.uint8)


# ------------------------------------------------- exact-path pinning


def test_exact_default_bitwise_unchanged_golden():
    """The exact path (and the DEFAULT dispatch) must reproduce the
    committed capture bit-for-bit on seeded inputs — the guard against
    a silent default flip or kernel drift."""
    g = np.load(GOLDEN)
    imgs, policy = jnp.asarray(g["images"]), jnp.asarray(g["policy"])
    key = jax.random.PRNGKey(99)
    out = A.apply_policy_batch(jnp.float32(imgs), policy, key)
    np.testing.assert_array_equal(np.asarray(out), g["out_policy_batch"])
    # the full train stack, through the DEFAULT dispatch argument
    out2 = cifar_train_batch(imgs, jax.random.PRNGKey(7), policy=policy,
                             cutout_length=8)
    np.testing.assert_array_equal(np.asarray(out2), g["out_train_batch"])
    # and explicitly spelled exact == default
    out3 = cifar_train_batch(imgs, jax.random.PRNGKey(7), policy=policy,
                             cutout_length=8, aug_dispatch="exact")
    np.testing.assert_array_equal(np.asarray(out3), g["out_train_batch"])


def test_unknown_dispatch_rejected():
    imgs = jnp.float32(_rand_imgs(0, b=4))
    with pytest.raises(ValueError, match="aug_dispatch"):
        cifar_train_batch(imgs, jax.random.PRNGKey(0),
                          aug_dispatch="typo")
    with pytest.raises(ValueError, match="groups"):
        A.apply_policy_batch_grouped(
            imgs, jnp.zeros((2, 1, 3)), jax.random.PRNGKey(0), groups=0)


# ------------------------------------------------- grouped semantics

# four sub-policies with deterministic, mutually-distinguishable effects
# (prob 1, no mirrored ops, no op-internal randomness): Invert,
# Brightness@0.1, Brightness@1.9, Solarize@128
_MARKER_POLICY = np.asarray([
    [[6, 1.0, 0.0]],
    [[12, 1.0, 0.0]],
    [[12, 1.0, 1.0]],
    [[8, 1.0, 0.5]],
], np.float32)


def _marker_candidates(imgs_f32):
    x = imgs_f32.astype(np.float32)
    inv = 255.0 - x
    b_lo = np.clip(np.trunc(x * 0.1), 0, 255)
    b_hi = np.clip(np.trunc(x * 1.9), 0, 255)
    sol = np.where(x < 128.0, x, 255.0 - x)
    return np.stack([inv, b_lo, b_hi, sol])  # [4, B, H, W, C]


def _identify_selection(out, candidates):
    """Per-image index of the candidate transform that produced it."""
    matches = (np.abs(candidates - np.asarray(out)[None]) < 0.5).all(
        axis=(2, 3, 4))  # [4, B]
    counts = matches.sum(axis=0)
    assert (counts == 1).all(), "ambiguous or unmatched grouped output"
    return matches.argmax(axis=0)  # [B]


def test_grouped_output_is_a_subpolicy_application_of_its_own_image():
    """Every grouped output must be SOME sub-policy applied to the SAME
    input image — validates the permutation/inverse-permutation plumbing
    end to end."""
    imgs = _rand_imgs(1, b=24)
    candidates = _marker_candidates(imgs)
    out = A.apply_policy_batch_grouped(
        jnp.float32(imgs), jnp.asarray(_MARKER_POLICY),
        jax.random.PRNGKey(5), groups=6)
    _identify_selection(out, candidates)  # asserts a unique match per image


@pytest.mark.slow
def test_grouped_determinism_and_key_sensitivity():
    imgs = jnp.float32(_rand_imgs(2, b=16))
    pol = jnp.asarray(_MARKER_POLICY)
    k = jax.random.PRNGKey(3)
    o1 = A.apply_policy_batch_grouped(imgs, pol, k, groups=4)
    o2 = A.apply_policy_batch_grouped(imgs, pol, k, groups=4)
    np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))
    o3 = A.apply_policy_batch_grouped(imgs, pol, jax.random.PRNGKey(4),
                                      groups=4)
    assert not np.array_equal(np.asarray(o1), np.asarray(o3))


@pytest.mark.slow
def test_grouped_prob_zero_policy_is_identity():
    imgs = jnp.float32(_rand_imgs(3, b=12))
    pol = jnp.float32([[[4, 0.0, 1.0], [0, 0.0, 1.0]],
                       [[6, 0.0, 1.0], [8, 0.0, 1.0]]])
    out = A.apply_policy_batch_grouped(imgs, pol, jax.random.PRNGKey(1),
                                       groups=3)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(imgs))


@pytest.mark.slow
def test_grouped_uneven_batch_and_group_clamp():
    """B not divisible by G (pad path) and G > B (clamp) both produce
    valid per-image sub-policy applications."""
    for b, g in ((10, 4), (3, 8)):
        imgs = _rand_imgs(40 + b, b=b)
        out = A.apply_policy_batch_grouped(
            jnp.float32(imgs), jnp.asarray(_MARKER_POLICY),
            jax.random.PRNGKey(b), groups=g)
        _identify_selection(out, _marker_candidates(imgs))


@pytest.mark.slow
def test_single_sub_grouped_is_bitwise_exact():
    """One sub-policy leaves nothing to stratify: the grouped kernel
    must short-circuit to the scalar path and match the exact kernel
    bit-for-bit — the property the audit / quality-gate lanes rely on."""
    imgs = jnp.float32(_rand_imgs(4, b=16))
    pol = jnp.float32([[[2, 0.7, 0.9], [14, 0.5, 0.6]]])  # TranslateX, Cutout
    key = jax.random.PRNGKey(11)
    exact = A.apply_policy_batch(imgs, pol, key)
    grouped = A.apply_policy_batch_grouped(imgs, pol, key, groups=4)
    np.testing.assert_array_equal(np.asarray(exact), np.asarray(grouped))
    # and through the full train stack
    u8 = _rand_imgs(5, b=16)
    se = cifar_train_batch(jnp.asarray(u8), key, policy=pol, cutout_length=8)
    sg = cifar_train_batch(jnp.asarray(u8), key, policy=pol, cutout_length=8,
                           aug_dispatch="grouped", aug_groups=4)
    np.testing.assert_array_equal(np.asarray(se), np.asarray(sg))


@pytest.mark.slow
def test_grouped_selection_stratified_and_marginally_uniform():
    """Statistical parity: per-image sub-policy marginals stay uniform
    (chi-square over many seeded batches) while within-batch counts are
    stratified — every sub-policy's count is a multiple of the chunk
    size, the grouped kernel's defining signature (i.i.d. exact draws
    would essentially never align to chunk multiples batch after
    batch)."""
    b, g, runs = 32, 8, 60
    chunk = b // g
    imgs = _rand_imgs(6, b=b)
    candidates = _marker_candidates(imgs)
    pol = jnp.asarray(_MARKER_POLICY)
    fn = jax.jit(lambda k: A.apply_policy_batch_grouped(
        jnp.float32(imgs), pol, k, groups=g))
    counts = np.zeros(4)
    for r in range(runs):
        sel = _identify_selection(fn(jax.random.PRNGKey(1000 + r)),
                                  candidates)
        per_batch = np.bincount(sel, minlength=4)
        assert (per_batch % chunk == 0).all(), (r, per_batch)
        counts += per_batch
    expected = counts.sum() / 4.0
    # chunks are the independent draws (g per run), not images
    chi2 = float((((counts / chunk) - (runs * g / 4.0)) ** 2
                  / (runs * g / 4.0)).sum())
    assert chi2 < 16.27, (chi2, counts)  # df=3, p=0.001
    assert counts.sum() == runs * b and expected > 0


@pytest.mark.slow
def test_grouped_gate_probability_stays_per_image():
    """`prob` gating must remain exactly per-image under grouping: the
    pooled fire rate matches the gate probability, and gates vary
    WITHIN chunks (an accidental per-chunk gate would make every chunk
    all-or-nothing)."""
    b, g, p_gate, runs = 32, 2, 0.5, 40
    chunk = b // g
    imgs = _rand_imgs(7, b=b)
    # two IDENTICAL subs: selection is irrelevant, only the gate acts
    pol = jnp.float32([[[6, p_gate, 0.0]], [[6, p_gate, 0.0]]])
    fn = jax.jit(lambda k: A.apply_policy_batch_grouped(
        jnp.float32(imgs), pol, k, groups=g))
    fired_total, interior_chunks, total_chunks = 0, 0, 0
    for r in range(runs):
        out = np.asarray(fn(jax.random.PRNGKey(2000 + r)))
        fired = (np.abs(out - imgs.astype(np.float32)) > 0.5).any(
            axis=(1, 2, 3))
        fired_total += int(fired.sum())
        # chunk membership is hidden by the permutation, but an
        # all-or-nothing per-chunk gate would force the BATCH fire count
        # to chunk multiples; count interior batches as evidence
        total_chunks += 1
        if 0 < int(fired.sum()) % chunk < chunk:
            interior_chunks += 1
    rate = fired_total / (runs * b)
    assert abs(rate - p_gate) < 0.05, rate  # n=1280, 3.6 sigma
    assert interior_chunks / total_chunks > 0.5, interior_chunks


# --------------------------------------------------- train-step wiring


def _probe_bn_model():
    """Tiny conv+BN model: exercises the full train-step machinery
    (mutable batch_stats, EMA-free state) at a fraction of a WRN's
    compile time — these tests guard augmentation WIRING, not model
    math."""
    from flax import linen as nn

    class ProbeBN(nn.Module):
        @nn.compact
        def __call__(self, x, train=False):
            x = nn.Conv(4, (3, 3))(x)
            x = nn.BatchNorm(use_running_average=not train)(x)
            x = nn.relu(x).mean(axis=(1, 2))
            return nn.Dense(10)(x)

    return ProbeBN()


def _train_pieces(aug_kw, stacked=False):
    from fast_autoaugment_tpu.ops.optim import build_optimizer
    from fast_autoaugment_tpu.train.steps import (
        create_train_state,
        make_stacked_train_step,
        make_train_step,
    )

    model = _probe_bn_model()
    opt = build_optimizer(
        {"type": "sgd", "decay": 2e-4, "clip": 5.0, "momentum": 0.9,
         "nesterov": True}, lambda s: 0.05)
    maker = make_stacked_train_step if stacked else make_train_step
    step = maker(model, opt, num_classes=10, cutout_length=4,
                 use_policy=True, **aug_kw)

    def fresh(seed=0):
        return create_train_state(model, opt, jax.random.PRNGKey(seed),
                                  jnp.zeros((2, 8, 8, 3), jnp.float32),
                                  use_ema=False)

    return step, fresh


# two subs, ONE op row each: enough to hit the genuine stratified path
# while compiling half the switches of a 2-op policy (compile time is
# what keeps these wiring tests inside the tier-1 budget)
_POLICY_2SUB = jnp.float32([[[6, 0.9, 0.0]], [[8, 0.9, 0.4]]])


@pytest.mark.slow
def test_train_step_exact_flag_is_default_bitwise():
    """Slow: near-tautological vs the current literals — the committed
    golden capture is the real default-flip guard (tier-1)."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.integers(0, 256, (8, 8, 8, 3), dtype=np.uint8))
    y = jnp.asarray(rng.integers(0, 10, (8,), np.int32))
    key = jax.random.PRNGKey(2)
    step_d, fresh = _train_pieces({})
    step_e, _ = _train_pieces({"aug_dispatch": "exact"})
    sd, md = step_d(fresh(), x, y, _POLICY_2SUB, key)
    se, me = step_e(fresh(), x, y, _POLICY_2SUB, key)
    for a, b in zip(jax.tree.leaves(sd.params), jax.tree.leaves(se.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert float(md["loss"]) == float(me["loss"])


@pytest.mark.slow
def test_train_step_grouped_runs_and_differs():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.integers(0, 256, (8, 8, 8, 3), dtype=np.uint8))
    y = jnp.asarray(rng.integers(0, 10, (8,), np.int32))
    key = jax.random.PRNGKey(2)
    step_e, fresh = _train_pieces({})
    step_g, _ = _train_pieces({"aug_dispatch": "grouped", "aug_groups": 4})
    se, me = step_e(fresh(), x, y, _POLICY_2SUB, key)
    sg, mg = step_g(fresh(), x, y, _POLICY_2SUB, key)
    assert np.isfinite(float(mg["loss"]))
    assert int(sg.step) == 1
    # different augmented batches -> different gradients (overwhelmingly)
    assert float(me["loss"]) != float(mg["loss"])


@pytest.mark.slow
def test_stacked_train_step_grouped_runs_and_masks():
    from fast_autoaugment_tpu.train.steps import stack_states

    rng = np.random.default_rng(2)
    k_folds = 2
    x = jnp.asarray(rng.integers(0, 256, (k_folds, 8, 8, 8, 3),
                                 dtype=np.uint8))
    y = jnp.asarray(rng.integers(0, 10, (k_folds, 8), np.int32))
    keys = jnp.stack([jax.random.PRNGKey(k) for k in range(k_folds)])
    step_g, fresh = _train_pieces(
        {"aug_dispatch": "grouped", "aug_groups": 4}, stacked=True)
    stacked = stack_states([fresh(0), fresh(1)])
    frozen_lane = jax.tree.map(lambda a: np.asarray(a[1]), stacked)
    active = jnp.asarray([1.0, 0.0], jnp.float32)
    new_states, metrics = step_g(stacked, x, y, _POLICY_2SUB, keys, active)
    assert np.isfinite(float(metrics["loss"][0]))
    assert float(metrics["num"][1]) == 0.0  # masked lane reports nothing
    for got, want in zip(jax.tree.leaves(
            jax.tree.map(lambda a: np.asarray(a[1]), new_states)),
            jax.tree.leaves(frozen_lane)):
        np.testing.assert_array_equal(got, want)  # bitwise pass-through


@pytest.mark.slow
def test_stacked_train_step_exact_flag_is_default_bitwise():
    """Slow: same rationale as the sequential flag-equality test; the
    stacked EXACT path's historical behavior is pinned by
    tests/test_stacked_phase1.py's parity suite (tier-1)."""
    from fast_autoaugment_tpu.train.steps import stack_states

    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.integers(0, 256, (2, 8, 8, 8, 3), dtype=np.uint8))
    y = jnp.asarray(rng.integers(0, 10, (2, 8), np.int32))
    keys = jnp.stack([jax.random.PRNGKey(k) for k in range(2)])
    active = jnp.ones((2,), jnp.float32)
    step_d, fresh = _train_pieces({}, stacked=True)
    step_e, _ = _train_pieces({"aug_dispatch": "exact"}, stacked=True)
    sd, md = step_d(stack_states([fresh(0), fresh(1)]), x, y, _POLICY_2SUB,
                    keys, active)
    se, me = step_e(stack_states([fresh(0), fresh(1)]), x, y, _POLICY_2SUB,
                    keys, active)
    for a, b in zip(jax.tree.leaves(sd.params), jax.tree.leaves(se.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(md["loss"]),
                                  np.asarray(me["loss"]))


# --------------------------------------------------------- TTA wiring


def _probe_model():
    from flax import linen as nn

    class Probe(nn.Module):
        @nn.compact
        def __call__(self, x, train=False):
            x = nn.Conv(4, (3, 3))(x)
            x = nn.relu(x).mean(axis=(1, 2))
            return nn.Dense(10)(x)

    return Probe()


def _probe_batch(seed=0, b=6, hw=8):
    rng = np.random.default_rng(seed)
    return {
        "x": jnp.asarray(rng.integers(0, 256, (b, hw, hw, 3),
                                      dtype=np.uint8)),
        "y": jnp.asarray(rng.integers(0, 10, (b,), np.int32)),
        "m": jnp.asarray(np.ones(b, np.float32)),
    }


@pytest.mark.slow
def test_audit_step_grouped_matches_exact():
    """The audit's S axis fixes the sub-policy per lane, so grouped
    dispatch changes NOTHING distributionally — outputs must match the
    exact path (per-lane sub-policies are single-sub: bitwise-equal
    augmentation, identical flattened forward)."""
    from fast_autoaugment_tpu.search.tta import make_audit_step

    model = _probe_model()
    batch = _probe_batch(0)
    variables = model.init(jax.random.PRNGKey(1),
                           batch["x"].astype(jnp.float32))
    subs = jnp.float32([[[6, 0.9, 0.0]],
                        [[2, 0.8, 1.0]],
                        [[12, 0.7, 0.8]]])  # [S=3, num_op=1, 3]
    key = jax.random.PRNGKey(9)
    exact = make_audit_step(model, num_policy=2, cutout_length=4)
    grouped = make_audit_step(model, num_policy=2, cutout_length=4,
                              aug_dispatch="grouped", aug_groups=3)
    oe = exact(variables["params"], {}, batch["x"], batch["y"], batch["m"],
               subs, key)
    og = grouped(variables["params"], {}, batch["x"], batch["y"], batch["m"],
                 subs, key)
    np.testing.assert_allclose(np.asarray(oe["correct_mean_sum"]),
                               np.asarray(og["correct_mean_sum"]),
                               rtol=0, atol=1e-6)
    assert float(oe["cnt"]) == float(og["cnt"])


@pytest.mark.slow
def test_tta_step_grouped_single_sub_matches_exact():
    """A single-sub candidate (the quality gate's identity baseline
    shape) through the grouped TTA step equals the exact step — the
    fixed-sub-per-lane case needs no distribution change."""
    from fast_autoaugment_tpu.search.tta import eval_tta, make_tta_step

    model = _probe_model()
    batches = [_probe_batch(0), _probe_batch(1)]
    variables = model.init(jax.random.PRNGKey(1),
                           batches[0]["x"].astype(jnp.float32))
    pol = jnp.float32([[[6, 0.8, 0.0]]])  # [1, num_op=1, 3]
    exact = make_tta_step(model, num_policy=2, cutout_length=4)
    grouped = make_tta_step(model, num_policy=2, cutout_length=4,
                            aug_dispatch="grouped", aug_groups=2)
    oe = eval_tta(exact, variables["params"], {}, batches, pol,
                  jax.random.PRNGKey(5))
    og = eval_tta(grouped, variables["params"], {}, batches, pol,
                  jax.random.PRNGKey(5))
    for field in ("minus_loss", "top1_valid", "top1_mean", "cnt"):
        assert float(oe[field]) == pytest.approx(float(og[field]),
                                                 abs=1e-6), field


@pytest.mark.slow
def test_tta_grouped_batched_matches_grouped_single():
    """K candidates through the grouped num_candidates=K step must equal
    the same K (policy, key) pairs through the grouped single-candidate
    step — the candidate axis only batches the forward, never the
    dispatch."""
    from fast_autoaugment_tpu.search.tta import (
        eval_tta,
        eval_tta_batched,
        make_tta_step,
    )

    model = _probe_model()
    batches = [_probe_batch(0), _probe_batch(1)]
    variables = model.init(jax.random.PRNGKey(1),
                           batches[0]["x"].astype(jnp.float32))
    k = 2
    rng = np.random.default_rng(8)
    # multi-sub policies with real op rows: the genuine stratified path
    ops = rng.integers(0, 15, (k, 2, 1, 1)).astype(np.float32)
    pl = rng.uniform(0.2, 1.0, (k, 2, 1, 2)).astype(np.float32)
    policies = jnp.asarray(np.concatenate([ops, pl], axis=-1))
    keys = jnp.stack([jax.random.PRNGKey(60 + i) for i in range(k)])
    single = make_tta_step(model, num_policy=2, cutout_length=4,
                           aug_dispatch="grouped", aug_groups=2)
    batched = make_tta_step(model, num_policy=2, cutout_length=4,
                            aug_dispatch="grouped", aug_groups=2,
                            num_candidates=k)
    got = eval_tta_batched(batched, variables["params"], {}, batches,
                           policies, keys)
    for i in range(k):
        want = eval_tta(single, variables["params"], {}, batches,
                        policies[i], keys[i])
        for field in ("minus_loss", "top1_valid", "top1_mean", "cnt"):
            assert got[i][field] == pytest.approx(want[field],
                                                  abs=1e-6), (i, field)


# ------------------------------------------------------- driver / CLI


@pytest.mark.slow
def test_search_driver_stamps_dispatch_mode(tmp_path):
    """A grouped search runs end-to-end and stamps the dispatch mode
    into its result artifact.  Slow: trains a real phase-1 fold model
    (the non-slow e2e coverage of the driver's exact path lives in
    tests/test_batched_search.py; the stamp/plumbing itself is also
    covered by test_cli_dispatch_flags + the unit parity tests)."""
    from fast_autoaugment_tpu.core.config import Config
    from fast_autoaugment_tpu.search.driver import search_policies

    conf = Config({
        "model": {"type": "wresnet10_1"},
        "dataset": "synthetic",
        "aug": "default",
        "cutout": 8,
        "batch": 8,
        "epoch": 1,
        "lr": 0.05,
        "lr_schedule": {"type": "cosine"},
        "optimizer": {"type": "sgd", "decay": 1e-4, "clip": 5.0,
                      "momentum": 0.9, "nesterov": True},
    })
    result = search_policies(
        conf, dataroot=str(tmp_path), save_dir=str(tmp_path / "search"),
        cv_num=1, cv_ratio=0.4, num_policy=2, num_op=1, num_search=2,
        num_top=1, aug_dispatch="grouped", aug_groups=2,
    )
    assert result["aug_dispatch"] == "grouped"
    assert result["aug_groups"] == 2
    assert result["final_policy_set"]
    # zero-recompile invariant holds for the grouped step too
    assert result["tta_executables"] in (
        None, result["tta_executables_expected"])


def test_cli_dispatch_flags():
    from fast_autoaugment_tpu.launch.search_cli import build_parser
    from fast_autoaugment_tpu.launch.train_cli import (
        build_parser as train_parser,
    )

    p = build_parser()
    args = p.parse_args(["-c", "x.yaml"])
    assert args.aug_dispatch == "exact" and args.aug_groups == 8
    args = p.parse_args(["-c", "x.yaml", "--aug-dispatch", "grouped",
                         "--aug-groups", "16"])
    assert args.aug_dispatch == "grouped" and args.aug_groups == 16
    with pytest.raises(SystemExit):
        p.parse_args(["-c", "x.yaml", "--aug-dispatch", "banana"])
    t = train_parser()
    args = t.parse_args(["-c", "x.yaml"])
    assert args.aug_dispatch == "exact" and args.aug_groups == 8
