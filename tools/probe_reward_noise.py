"""Measure the TTA reward noise the TPE optimizer actually faces.

VERDICT r3, weak 3: the TPE-vs-random table (docs/SEARCH_QUALITY.md)
shows TPE's edge vanishing past reward noise sigma ~0.05, and the
driver's defense (the fold-quality gate keeps oracles strong enough
that sigma stays ~0.02) was validated only on glyph tasks.  This probe
measures sigma directly at any search shape: load the phase-1 fold
checkpoints of a finished (or partial) search run, evaluate a handful
of fixed candidate policies repeatedly with fresh augmentation draws,
and report the per-policy std of `top1_valid` — the quantity TPE
conditions on.

    python tools/probe_reward_noise.py <save_dir> -c confs/....yaml \
        [--dataroot ./data] [--folds 0] [--policies 3] [--draws 8]

Emits one JSON line: per-fold sigma estimates + the pooled estimate,
comparable against the noise grid of docs/SEARCH_QUALITY.md.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("save_dir")
    p.add_argument("-c", "--conf", required=True)
    p.add_argument("--dataroot", default="./data")
    p.add_argument("--cv-ratio", type=float, default=0.4)
    p.add_argument("--folds", default="0")
    p.add_argument("--policies", type=int, default=3)
    p.add_argument("--draws", type=int, default=8)
    p.add_argument("--num-policy", type=int, default=5)
    p.add_argument("--num-op", type=int, default=2)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("override", nargs="*",
                   help="dotted conf overrides, e.g. dataset=... (must "
                        "match the search run's, or the checkpoint paths "
                        "and fold data will not line up)")
    args = p.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from fast_autoaugment_tpu.core.config import load_config
    from fast_autoaugment_tpu.parallel.mesh import make_mesh
    from fast_autoaugment_tpu.policies.archive import policy_decoder, policy_to_tensor
    from fast_autoaugment_tpu.search.driver import (
        _FoldEval,
        _fold_ckpt_path,
        make_search_space,
    )
    from fast_autoaugment_tpu.search.tpe import TPE

    conf = load_config(args.conf, overrides=args.override)
    mesh = make_mesh()
    evaluator = _FoldEval(conf, args.dataroot, mesh,
                          num_policy=args.num_policy, num_op=args.num_op,
                          cv_ratio=args.cv_ratio, seed=args.seed)

    # sample candidate policies the way phase 2 does (TPE startup draws)
    tpe = TPE(make_search_space(args.num_policy, args.num_op), seed=args.seed)
    cands = [policy_decoder(tpe.suggest(), args.num_policy, args.num_op)
             for _ in range(args.policies)]

    out = {"metric": "tta_reward_noise", "draws": args.draws,
           "policies": args.policies, "folds": {}}
    sigmas = []
    for fold in [int(f) for f in args.folds.split(",")]:
        path = _fold_ckpt_path(args.save_dir, conf, fold, args.cv_ratio)
        if not os.path.exists(path):
            print(f"[noise] fold {fold}: no checkpoint at {path} — skipped",
                  file=sys.stderr)
            continue
        params, batch_stats = evaluator.load_fold(path)
        fold_stats = []
        for ci, cand in enumerate(cands):
            pol_t = jnp.asarray(policy_to_tensor(cand))
            vals = [
                evaluator.evaluate(
                    fold, params, batch_stats, pol_t,
                    jax.random.PRNGKey(1000 * fold + 37 * ci + d),
                )["top1_valid"]
                for d in range(args.draws)
            ]
            fold_stats.append({
                "mean": float(np.mean(vals)),
                "sigma": float(np.std(vals, ddof=1)),
            })
            sigmas.append(fold_stats[-1]["sigma"])
        out["folds"][str(fold)] = fold_stats
    if not sigmas:
        print("[noise] no folds probed", file=sys.stderr)
        return 1
    out["sigma_pooled"] = float(np.sqrt(np.mean(np.square(sigmas))))
    out["tpe_edge_context"] = (
        "docs/SEARCH_QUALITY.md: TPE beats random for sigma <= 0.02, "
        "parity by sigma ~0.05-0.1"
    )
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
