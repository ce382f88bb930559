"""The planted-policy reward: a reference the TPE tests compare against.

A hidden target policy is planted on the REAL 30-D search space
(``make_search_space(5, 2)``: 10 x choice(15) + 20 x U(0,1)).  Each
(sub-policy, op) slot scores partial credit — op-identity match (the
categorical part) gated with Gaussian closeness of prob and level (the
continuous part) — plus observation noise: flat elsewhere, multi-modal
across slots, mixed categorical/continuous, like the density-matching
objective.  Pure random search is the no-model control; the results on
the full budget x noise grid are in docs/SEARCH_QUALITY.md.
"""

from __future__ import annotations

import numpy as np

from fast_autoaugment_tpu.search.driver import make_search_space
from fast_autoaugment_tpu.search.tpe import TPE

NUM_POLICY, NUM_OP, NUM_OPS = 5, 2, 15


def plant_target(rng) -> dict:
    t = {}
    for i in range(NUM_POLICY):
        for j in range(NUM_OP):
            t[f"policy_{i}_{j}"] = int(rng.integers(0, NUM_OPS))
            t[f"prob_{i}_{j}"] = float(rng.uniform())
            t[f"level_{i}_{j}"] = float(rng.uniform())
    return t


def make_reward(target: dict, noise: float, rng):
    """Partial-credit closeness to the planted policy, in [0, ~1].
    Returns (observed_fn, true_fn): observed adds N(0, noise) per
    evaluation; true is the noiseless value."""

    def true_fn(x: dict) -> float:
        s = 0.0
        for i in range(NUM_POLICY):
            for j in range(NUM_OP):
                if x[f"policy_{i}_{j}"] == target[f"policy_{i}_{j}"]:
                    dp = x[f"prob_{i}_{j}"] - target[f"prob_{i}_{j}"]
                    dl = x[f"level_{i}_{j}"] - target[f"level_{i}_{j}"]
                    s += float(np.exp(-0.5 * (dp / 0.2) ** 2)
                               * np.exp(-0.5 * (dl / 0.2) ** 2))
        return s / (NUM_POLICY * NUM_OP)

    def observed_fn(x: dict) -> float:
        return true_fn(x) + float(rng.normal(0, noise))

    return observed_fn, true_fn


def driver_n_startup(trials: int) -> int:
    """The startup rule phase 2 uses (search/driver.py): hyperopt's 20
    at reference budgets, proportional at small ones."""
    return min(20, max(5, trials // 4))


def run_strategy(strategy: str, trials: int, seed: int, noise: float,
                 n_startup: int | None = None) -> np.ndarray:
    """TRUE reward of the incumbent (best-by-OBSERVED) after each trial.

    Under observation noise, best-so-far *observed* reward is inflated
    by lucky noise draws; what phase 2 actually consumes is the ranking
    by observed reward (top-N selection, search.py:253-259), so the
    honest quality metric is the noiseless value of the trial the
    optimizer would rank first."""
    rng = np.random.default_rng((seed, 1))  # observation noise
    # distinct stream from TPE(seed=seed)'s sampler — identical streams
    # would make the first random proposal BE the planted target
    target = plant_target(np.random.default_rng((seed, 2)))
    observed_fn, true_fn = make_reward(target, noise, rng)
    space = make_search_space(NUM_POLICY, NUM_OP)
    opt = TPE(space, seed=seed,
              n_startup=n_startup if n_startup is not None
              else driver_n_startup(trials))
    curve = np.empty(trials)
    best_obs, best_true = -np.inf, 0.0
    for t in range(trials):
        x = opt._random_sample() if strategy == "random" else opt.suggest()
        r = observed_fn(x)
        opt.tell(x, r)
        if r > best_obs:
            best_obs, best_true = r, true_fn(x)
        curve[t] = best_true
    return curve


def run_cell(trials: int, noise: float, runs: int):
    """(wins, gain, means) for one (budget, noise) cell over paired seeds."""
    finals = {}
    for strat in ("random", "tpe"):
        finals[strat] = np.array([
            run_strategy(strat, trials, seed, noise)[-1]
            for seed in range(runs)
        ])
    wins = int((finals["tpe"] > finals["random"]).sum())
    ties = int((finals["tpe"] == finals["random"]).sum())
    gain = float(finals["tpe"].mean() - finals["random"].mean())
    return {
        "trials": trials, "noise": noise, "wins": wins, "ties": ties,
        "runs": runs, "gain": gain,
        "random_mean": float(finals["random"].mean()),
        "random_std": float(finals["random"].std()),
        "tpe_mean": float(finals["tpe"].mean()),
        "tpe_std": float(finals["tpe"].std()),
    }
